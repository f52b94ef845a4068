"""Metric arithmetic on what a run recorded: percentiles, request timings
from the due instant, tokens inside a window.  Plain Python, so the tests can check it on
hand-made records."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) by linear interpolation between the two
    nearest ranks, as numpy's default does.  An infinite value (a failed
    request) sorts last and is returned as it is."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = int(math.floor(pos)), int(math.ceil(pos))
    if lo == hi or math.isinf(xs[hi]):
        return float(xs[hi])
    return float(xs[lo] + (xs[hi] - xs[lo]) * (pos - lo))


def in_window(records, t0: float, seconds: float) -> list:
    """Requests that were due inside [t0, t0 + seconds)."""
    return [r for r in records if t0 <= r["due"] < t0 + seconds]


def ttft_ms(record) -> float:
    """Due time (open loop: when the schedule said; closed loop: the instant
    its client came free; never when the generator got round to it) to the
    first token at the client.  A request that failed, was refused or
    produced nothing counts as infinite."""
    if record.get("error") or not record["token_times"]:
        return math.inf
    return (record["token_times"][0] - record["due"]) * 1e3


def tpot_ms(record) -> float | None:
    """Mean gap between one request's output tokens at the client; None for
    a request of fewer than two tokens, infinite for a failed one."""
    if record.get("error"):
        return math.inf
    times = record["token_times"]
    if len(times) < 2:
        return None
    return (times[-1] - times[0]) / (len(times) - 1) * 1e3


def token_gaps_ms(records) -> list:
    """Every gap between consecutive output tokens of one request, over all
    requests that ran without error."""
    gaps = []
    for r in records:
        if r.get("error"):
            continue
        times = r["token_times"]
        gaps.extend((b - a) * 1e3 for a, b in zip(times, times[1:]))
    return gaps


def lateness_ms(records) -> list:
    """How long after its due time each request was actually sent."""
    return [(r["sent"] - r["due"]) * 1e3 for r in records
            if r.get("sent") is not None]


def tokens_in_window(records, t0: float, seconds: float) -> int:
    """Output tokens that reached the client inside [t0, t0 + seconds),
    whichever request they belong to."""
    t1 = t0 + seconds
    return sum(t0 <= t < t1 for r in records for t in r["token_times"])


def request_failed(record) -> bool:
    """Errored, refused, or ran to its end with the wrong number of tokens.
    A request the harness cut when the window closed is not a failure."""
    if record.get("error"):
        return True
    if record.get("cut"):
        return False
    return len(record["token_times"]) != record["max_new_tokens"]


def live_context_tokens(records, t: float) -> int:
    """Tokens of context the requests in flight at time `t` hold: each one's
    prompt plus the output tokens it has received by then."""
    total = 0
    for r in records:
        times = r["token_times"]
        if not times or r.get("error") or not times[0] <= t <= times[-1]:
            continue
        total += r["prompt_len"] + sum(x <= t for x in times)
    return total


def slice_mean(run: dict, fn, instants: int = 16):
    """The mean of `fn(records, t)` at `instants` evenly spaced moments of
    the traced slice [trace_on, trace_on + slice_s], or None where no
    profiler session was opened and closed.  The `trace_off` mark is set
    when the profiler has written its file, seconds to minutes after the
    slice's end, so it only says that a session was closed and caps a slice
    cut short; nothing read here moves with when it was set.  Not one
    moment either: a lane that ends or starts moves the total by 4%."""
    marks = run.get("marks", {})
    if "trace_on" not in marks or "trace_off" not in marks:
        return None
    on = marks["trace_on"]
    span = min(float(run["traffic"]["trace"]["slice_s"]),
               marks["trace_off"] - on)
    return sum(fn(run["records"], on + span * (i + 0.5) / instants)
               for i in range(instants)) / instants


def slice_context_tokens(run: dict, instants: int = 16):
    """`live_context_tokens` over the traced slice (`slice_mean`): what a
    paged kernel's time in the slice is divided by."""
    return slice_mean(run, live_context_tokens, instants)


def spread(values) -> float:
    """Distance between the quartiles over the median: the driver's measure
    of run-to-run spread."""
    return ((percentile(values, 75) - percentile(values, 25))
            / percentile(values, 50))
