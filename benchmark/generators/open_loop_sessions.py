"""Request lists for the serve cells, from parameters alone: an open loop's
schedule of arrivals, or the list a closed loop of clients works through.

One generator covers the mixes the benchmark has and the ones PERF.md keeps
for later (independent requests, sessions with shared heads and growing
history), so that a new mix is a new data file.  Which loop a file is
follows from one key: with `clients` it is closed (the driver sends a
client's next request when its last one has ended, so the list has no
arrival times and is the same for any window), without it open.  Every draw
comes from `--seed`: arrival times, lengths, which session speaks, when one
restarts, and every token.  The program's answers never feed back into a
prompt (a session's history holds seeded stand-ins for the earlier answers),
so the seed alone fixes the schedule and every prompt.  A mix is made steady from
seed to seed by its parameters (a lead-in that fills the lanes; a rate well
to one side of the knee, or more clients than lanes), never by pinning the
schedule.

Parameters (traffic file, under `requests` unless said otherwise):
  rate_rps (top level)   open loop: mean arrivals per second, a Poisson
                         process
  clients (top level)    closed loop: how many requests are in flight; every
                         request of the list is due at -lead_in_s and the
                         driver sends it when a client is free
  count                  closed loop: the length of the list (it has to
                         outlast the window at any speed of the program)
  lead_in_s, tail_s      the schedule runs from -lead_in_s to seconds +
                         tail_s; only requests due in [0, seconds) count
  fill_requests          that many requests due at -lead_in_s (open loop:
                         besides the arrivals; closed loop: the first of the
                         list), their outputs scaled by a uniform draw:
                         lanes filled with requests at every stage, as a
                         long-running server's
  prompt_len, output_len {"dist": "uniform" | "log_uniform", "lo", "hi"};
                         prompt_len is the new turn of each request
  sessions               null, or {"count", "groups", "grouped_share",
                         "head_len", "max_prompt", "preroll_turns",
                         "restart_prob"}: the first grouped_share of the
                         sessions are dealt over `groups` groups that each
                         share a head of head_len tokens; a request is head +
                         history + new turn, the history then grows by the
                         turn and a stand-in answer, and starts over once
                         the next prompt would pass max_prompt, or, with
                         probability restart_prob after any turn, because
                         its user left and another took the place; each
                         session starts with 0 to preroll_turns turns
                         already behind it
"""

from __future__ import annotations

import math

import numpy as np


def _length(rng, spec) -> int:
    lo, hi = spec["lo"], spec["hi"]
    if spec["dist"] == "uniform":
        return int(rng.integers(lo, hi + 1))
    if spec["dist"] == "log_uniform":
        return int(round(math.exp(rng.uniform(math.log(lo), math.log(hi)))))
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def _arrivals(rng, rate, start, end) -> list:
    """Arrival times in [start, end) of a Poisson process."""
    times, t = [], start
    while True:
        t += rng.exponential(1.0 / rate)
        if t >= end:
            return times
        times.append(t)


class _Session:
    def __init__(self, shape_rng, token_rng, head, spec, p, vocab):
        self.shape, self.rng, self.head = shape_rng, token_rng, head
        self.spec, self.p, self.vocab = spec, p, vocab
        self.history: list = []
        for _ in range(int(shape_rng.integers(0, spec["preroll_turns"] + 1))):
            self.next_turn()

    def _tokens(self, n):
        return self.rng.integers(0, self.vocab, n).tolist()

    def next_turn(self):
        turn = self._tokens(_length(self.shape, self.p["prompt_len"]))
        out_len = _length(self.shape, self.p["output_len"])
        if len(self.head) + len(self.history) + len(turn) \
                > self.spec["max_prompt"] \
                or self.shape.uniform() < self.spec["restart_prob"]:
            self.history = []
        prompt = self.head + self.history + turn
        self.history = self.history + turn + self._tokens(out_len)
        return prompt, out_len


def make(traffic: dict, seed: int, seconds: float, vocab_size: int) -> list:
    """The whole schedule: dicts with `id`, `due` (seconds from the opening
    of the window; negative during the lead-in), `prompt` (token ids),
    `max_new_tokens`, `session` (-1 if none) and `group` (-1 if none),
    sorted by due time."""
    p = traffic["requests"]
    # Independent streams, so that a longer window only appends arrivals.
    rng_arrive, rng_fill, rng_pick, rng_len, rng_free = (
        np.random.default_rng([seed, i]) for i in range(5))
    start, end = -float(p["lead_in_s"]), float(seconds) + float(p["tail_s"])
    fill = int(p.get("fill_requests", 0))
    if "clients" in traffic:
        times = [start] * int(p["count"])
    else:
        times = [start] * fill + _arrivals(rng_arrive, traffic["rate_rps"],
                                           start, end)
    spec = p.get("sessions")
    sessions = []
    if spec:
        heads = [np.random.default_rng([seed, 10, g]).integers(
            0, vocab_size, spec["head_len"]).tolist()
            for g in range(spec["groups"])]
        grouped = int(round(spec["count"] * spec["grouped_share"]))
        for s in range(spec["count"]):
            group = s % spec["groups"] if s < grouped else -1
            sessions.append((group, _Session(
                np.random.default_rng([seed, 12, s]),
                np.random.default_rng([seed, 11, s]),
                heads[group] if group >= 0 else [], spec, p, vocab_size)))
    requests = []
    for i, due in enumerate(times):
        if sessions:
            s = int(rng_pick.integers(0, len(sessions)))
            group, session = sessions[s]
            prompt, out_len = session.next_turn()
        else:
            s = group = -1
            prompt = rng_free.integers(
                0, vocab_size, _length(rng_len, p["prompt_len"])).tolist()
            out_len = _length(rng_len, p["output_len"])
        if i < fill:
            out_len = max(2, int(math.ceil(out_len * rng_fill.uniform())))
        requests.append({"id": i, "due": float(due), "prompt": prompt,
                         "max_new_tokens": out_len, "session": s,
                         "group": group})
    return requests
