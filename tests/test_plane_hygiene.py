"""Static plane hygiene (PR 11 satellite): every literal call site of
``events.record(...)`` / ``spans.begin(...)`` / ``spans.span(...)`` /
``spans.phase(...)`` in the package uses a plane string from ``events.PLANES`` and a sane kind,
and every file that opens spans imperatively also closes them.  Greps
source so a typo'd plane ("sched " / "schedule") fails CI instead of
silently fragmenting the `cli top` per-plane rates.
"""

import pathlib
import re

from ray_tpu.util import events

PKG = pathlib.Path(events.__file__).resolve().parents[1]

# events.record("plane", "kind", ... / spans.begin("plane", "kind", ...
# Payloads stay on later lines; plane+kind may wrap one line break.
_CALL = re.compile(
    r"(?:events\.record|spans\.begin|spans\.span|spans\.phase)\(\s*\n?\s*"
    r"(['\"])([^'\"]*)\1\s*,\s*\n?\s*(['\"])([^'\"]*)\3",
    re.MULTILINE)

_KIND_OK = re.compile(r"^[a-z][a-z0-9_]*$")


def _call_sites():
    for path in sorted(PKG.rglob("*.py")):
        text = path.read_text()
        for m in _CALL.finditer(text):
            line = text[:m.start()].count("\n") + 1
            yield path.relative_to(PKG.parent), line, m.group(2), \
                m.group(4)


def test_call_sites_exist():
    sites = list(_call_sites())
    # The suite is vacuous if the grep regex rots; PR 11 alone
    # instruments dozens of sites.
    assert len(sites) > 30, f"grep found only {len(sites)} sites"


def test_planes_are_registered():
    """A plane is one of `events.PLANES`, or `<plane>.<phase>` for a part
    nested in the `spans.phase` of that plane and kind (the engine's
    `engine.build_batch/upload`: a profiler annotation under a name no
    reader of the flat `engine/` phases sees; it writes nothing to the
    ring, so it is no plane of the recorder's)."""
    sites = {(pl, k) for _, _, pl, k in _call_sites()}
    bad = [(str(f), ln, pl, k) for f, ln, pl, k in _call_sites()
           if pl not in events.PLANES
           and not (pl.count(".") == 1 and tuple(pl.split(".")) in sites
                    and pl.split(".")[0] in events.PLANES)]
    assert not bad, f"unregistered plane strings: {bad}"


def test_kinds_are_snake_case():
    bad = [(str(f), ln, pl, k) for f, ln, pl, k in _call_sites()
           if not _KIND_OK.match(k)]
    assert not bad, f"malformed span/event kinds: {bad}"


def test_imperative_begins_have_ends():
    """A file using spans.begin() must also call spans.end() — the token
    API is imperative, so a file-local end is the only way a begin can
    ever close (the context form needs no end)."""
    offenders = []
    for path in sorted(PKG.rglob("*.py")):
        text = path.read_text()
        if "spans.begin(" in text and "spans.end(" not in text \
                and path.name != "spans.py":
            offenders.append(str(path.relative_to(PKG.parent)))
    assert not offenders, \
        f"files that begin spans but never end any: {offenders}"


def test_control_plane_span_kinds_present():
    """The batched control plane (PR 14) is attributable only because
    these spans exist: scale_attrib's actor_storm mode needs the spawn
    path (fork/boot), `cli analyze` needs gcs/flush, and the batched
    lease/dispatch path keeps the PR 11 per-task kinds.  Losing any of
    them silently blinds the attribution tooling, so pin them here."""
    sites = {(pl, k) for _, _, pl, k in _call_sites()}
    required = {
        ("sched", "zygote_fork"),   # hostd: batched fork via the zygote
        ("sched", "worker_boot"),   # hostd: fork -> worker_ready
        ("gcs", "flush"),           # gcs: coalesced write_rows commit
        ("sched", "lease_wait"),    # driver: one per (batched) lease RPC
        ("sched", "dispatch"),      # driver: still one per task
        ("sched", "inflight"),      # driver: shipped -> push completion
    }
    missing = required - sites
    assert not missing, f"control-plane span kinds vanished: {missing}"


def test_span_kinds_do_not_collide_with_instant_kinds():
    """One (plane, kind) must be either always-instant or always-span:
    build_breakdown keys phases by (plane, kind), so a mixed kind would
    split its statistics.  Known exceptions: none."""
    span_kinds, instant_kinds = set(), set()
    spans_call = re.compile(
        r"(spans\.begin|spans\.span|events\.record)\(\s*\n?\s*"
        r"(['\"])([^'\"]*)\2\s*,\s*\n?\s*(['\"])([^'\"]*)\4",
        re.MULTILINE)
    for path in sorted(PKG.rglob("*.py")):
        if path.name in ("spans.py", "events.py"):
            continue
        for m in spans_call.finditer(path.read_text()):
            key = (m.group(3), m.group(5))
            if m.group(1) == "events.record":
                instant_kinds.add(key)
            else:
                span_kinds.add(key)
    mixed = span_kinds & instant_kinds
    # serve/admit intentionally exists in both forms: the instant event
    # is the always-on SLO sample, the span only appears under a trace.
    mixed -= {("serve", "admit")}
    assert not mixed, f"(plane, kind) used as both span and instant: {mixed}"

def test_pp_span_kinds_present():
    """The MPMD pipeline trainer (PR 15) is attributable only because
    these spans exist: scale_attrib's pp mode derives the bubble
    fraction from the unattributed remainder of stage_fwd/stage_bwd/
    xfer/apply/ckpt/recover, and the chaos gates key on the stage_dead/
    replay/rollback instants.  Pin them so refactors cannot silently
    blind the tooling."""
    sites = {(pl, k) for _, _, pl, k in _call_sites()}
    required_spans = {
        ("pp", "stage_fwd"),    # stage actor: one microbatch forward
        ("pp", "stage_bwd"),    # stage actor: one microbatch backward
        ("pp", "xfer"),         # stage actor: BLOCKING inter-stage fetch
        ("pp", "xfer_overlap"),  # stage actor: prefetch-thread fetch,
                                 # concurrent with compute (PR 18)
        ("pp", "recv_wait"),    # stage actor: compute waits on an
                                # in-flight prefetch (exposed overlap)
        ("pp", "apply"),        # stage actor: fold partials + SGD update
        ("pp", "ckpt"),         # stage actor: per-stage sharded save
        ("pp", "step"),         # driver: whole pipeline step
        ("pp", "recover"),      # driver: reform/replay/rollback window
    }
    required_instants = {
        ("pp", "bubble"),       # stage actor: idle gap between ops
        ("pp", "stage_dead"),   # driver: a gang was declared dead
        ("pp", "replay"),       # driver: surgical in-place replay chosen
        ("pp", "rollback"),     # driver: global rollback chosen
        ("pp", "prepush"),      # driver: activation ref shipped into a
                                # downstream receive window
        ("pp", "placement"),    # driver: topology placement plan applied
    }
    missing = (required_spans | required_instants) - sites
    assert not missing, f"pp plane kinds vanished: {missing}"


def test_pp_compute_spans_are_chunk_tagged():
    """The interleaved schedule (PR 18) multiplexes several stage-chunks
    onto one gang; attribution and debugging need the chunk id on every
    compute/transfer span.  Pin the tag at the call sites so a refactor
    cannot silently collapse chunks back into an undifferentiated
    stage."""
    src = (PKG / "train" / "pipeline_stage.py").read_text()
    for kind in ("stage_fwd", "stage_bwd", "xfer", "xfer_overlap",
                 "recv_wait"):
        m = re.search(
            r'spans\.(?:span|begin)\(\s*"pp",\s*"%s",([^)]*)\)' % kind,
            src)
        assert m, f"pp/{kind} span call site not found"
        assert "chunk=" in m.group(1), \
            f"pp/{kind} span lost its chunk= tag"


def test_kv_plane_kinds_present():
    """The disaggregated-serving plane (serve/kv_tier) is attributable
    only through these kinds: scale_attrib's serve mode carves request
    wall into route/prefill/kv_xfer/decode via the spans, and the chaos
    gates + bench key on the tier/handoff instants.  Pin them so
    refactors cannot silently blind the tooling."""
    sites = {(pl, k) for _, _, pl, k in _call_sites()}
    required_spans = {
        ("kv", "export"),        # engine: gather sealed chain for handoff
        ("kv", "import"),        # engine: adopt a shipped chain
        ("kv", "handoff"),       # handle: prefill hop + frame transfer
    }
    required_instants = {
        ("kv", "spilled"),       # tier: block left the device pool
        ("kv", "restored"),      # tier: spilled block rejoined the pool
        ("kv", "dropped"),       # tier: block fell off the last tier
        ("kv", "handoff_lost"),  # handle: prefill died, decode re-prefills
        ("serve", "prefix_route"),  # router: prefix affinity won a pick
    }
    missing = (required_spans | required_instants) - sites
    assert not missing, f"kv plane kinds vanished: {missing}"


def test_rl_plane_kinds_present():
    """The Podracer actor/learner substrate (PR 20) is attributable only
    because these kinds exist: scale_attrib's rl mode carves wall into
    rollout/learn/publish/adopt via the spans, and the chaos gates +
    staleness accounting key on the instants.  Pin them so refactors
    cannot silently blind the tooling."""
    sites = {(pl, k) for _, _, pl, k in _call_sites()}
    required_spans = {
        ("rl", "publish"),        # driver: one put + gang-wide adopt fan-out
        ("rl", "adopt"),          # actor: in-place weight swap (live lanes)
        ("rl", "rollout"),        # actor: one versioned fragment/episode gang
        ("rl", "learn"),          # learner: one V-trace SGD step
    }
    required_instants = {
        ("rl", "stale_drop"),     # queue: batch beyond the staleness bound
        ("rl", "backpressure"),   # queue: producer held, queue full
        ("rl", "worker_replaced"),  # controller: rollout gang re-formed
        ("rl", "learner_resume"),   # learner: restored from COMMITTED ckpt
        ("engine", "weights_swap"),  # engine: params swapped between steps
    }
    missing = (required_spans | required_instants) - sites
    assert not missing, f"rl plane kinds vanished: {missing}"


def test_gcs_ft_event_kinds_present():
    """The head-survival plane (PR 16) is observable only through these
    instants: the availability bench and the chaos gates key on the
    kill/restore/fence records, and `cli events` surfaces outages via
    unreachable/reconnected.  Pin them so refactors cannot silently
    blind the recovery tooling."""
    sites = {(pl, k) for _, _, pl, k in _call_sites()}
    required = {
        ("gcs", "restored"),            # gcs: tables rebuilt from sqlite
        ("gcs", "node_fenced"),         # gcs: stale re-register refused
        ("gcs", "node_resync"),         # gcs: anti-entropy snapshot applied
        ("gcs", "chaos_kill"),          # gcs: scripted pre-request kill
        ("gcs", "chaos_kill_flush"),    # gcs: scripted mid-flush kill
        ("gcs", "supervisor_respawn"),  # launcher: head respawned in place
        ("gcs", "supervisor_gave_up"),  # launcher: restart budget spent
        ("gcs", "unreachable"),         # client/hostd: outage onset
        ("gcs", "reconnected"),         # client: outage over, duration
        ("link", "blackhole"),          # chaos: partition window opened
        ("link", "heal"),               # chaos: partition window closed
        ("proc", "node_fenced"),        # hostd: killed own stale workers
        ("proc", "stale_actor_reaped"), # hostd: one failed-over actor gone
        ("serve", "stale_routing"),     # router: served on cache in outage
    }
    missing = required - sites
    assert not missing, f"gcs-ft event kinds vanished: {missing}"


def test_engine_step_phases_present_and_only_in_the_profilers_trace():
    """The decode cell's idle split (benchmark/idle_phases.py) and the
    `engine/step` record's durations name these five phases; each is a
    `spans.phase` (a profiler annotation and a number for the caller),
    never a ring span: two ring slots a phase would flood the recorder at
    a dozen steps a second."""
    src = (PKG / "inference" / "engine.py").read_text()
    phases = set(re.findall(r'spans\.phase\(\s*"engine",\s*"(\w+)"\)', src))
    assert phases == {"admit", "build_batch", "dispatch", "fetch", "commit"}
    ring = {k for _, _, pl, k in _call_sites() if pl == "engine"} - phases
    assert {"step", "submit", "prefill", "queue", "decode"} <= ring
    for path in sorted(PKG.rglob("*.py")):
        for m in re.finditer(
                r'(?:spans\.begin|spans\.span|events\.record)\(\s*\n?\s*'
                r'"engine",\s*\n?\s*"(\w+)"', path.read_text()):
            assert m.group(1) not in phases, (path.name, m.group(1))


def test_expert_load_is_fetched_by_stats_alone_and_kernels_keep_their_names():
    """`stats()["moe"]` reads counters the step sums up on the device
    (forward_cached's `moe_load`); the one host fetch of them sits in
    `_moe_stats`, so an expert configuration adds no transfer and no ring
    event to a step.  The benchmark's readers find the two kernels of its
    step by the names their `pallas_call`s give."""
    src = (PKG / "inference" / "engine.py").read_text()
    fetches = re.findall(r"np\.asarray\(self\._moe_load\)", src)
    assert len(fetches) == 1
    body = src[src.index("def _moe_stats"):src.index("def compiled_steps")]
    assert fetches[0] in body
    # the step loop touches the buffer only to hand it on
    step_body = src[src.index("    def step(self)"):
                    src.index("    def _build_batch")]
    assert "_moe_load" not in step_body and "moe" not in step_body
    names = {
        "moe.py": re.findall(r'name="(\w+)"',
                             (PKG / "ops" / "moe.py").read_text()),
        "attention.py": re.findall(
            r'name="(\w+)"', (PKG / "ops" / "attention.py").read_text())}
    # (the grouped multiply's backward has a kernel of its own since PR 61,
    # `dw`; forward and dx are the one kernel under the one name; since PR
    # 62 the train path's way back to the tokens is `moe_combine`)
    assert names["moe.py"] == ["moe_grouped_matmul_dw", "moe_grouped_matmul",
                               "moe_combine"]
    assert "paged_decode_attention" in names["attention.py"]


def test_weights_are_prepared_at_load_and_swap_and_never_in_a_step():
    """`engine/weights_prepare` is one ring event per preparation of the
    served weights: `_prepare` records it, and only the constructor and
    `update_params` call `_prepare`; the step hands `_served` on as it is
    (a cast or a copy there would be back in every token's path)."""
    src = (PKG / "inference" / "engine.py").read_text()
    sites = [(pl, k) for path, _, pl, k in _call_sites()
             if k == "weights_prepare"]
    assert sites == [("engine", "weights_prepare")]
    body = src[src.index("    def _prepare(self"):
               src.index("    def update_params(self")]
    assert '"weights_prepare"' in body
    assert len(re.findall(r"self\._prepare\(", src)) == 2
    init = src[src.index("    def __init__(self, model="):
               src.index("    # ---------------- public API")]
    swap = src[src.index("    def update_params(self"):
               src.index("    # -------- disaggregated prefill/decode")]
    assert "self._prepare(params)" in init and "self._prepare(params)" in swap
    step_path = src[src.index("    def step(self)"):
                    src.index("    def _make_step_fn")]
    assert "serving_params" not in step_path and "_prepare" not in step_path
    assert step_path.count("self._served") == 2     # avals, and the call


def test_the_engine_has_one_loop_and_no_option_sets_its_depth():
    """The scheduler runs a step ahead of its results (PERF.md section 6,
    PR 30) in the one `step()` there is: each phase opened once, `dispatch`
    before `fetch` before `commit`; one `engine/step` record an iteration,
    carrying `ahead`; what is in flight kept in one place, and how deep the
    loop runs decided there from whether a proposer needs the fetched
    token.  No keyword, flag or environment variable chooses it."""
    import ast
    src = (PKG / "inference" / "engine.py").read_text()
    step_body = src[src.index("    def step(self)"):
                    src.index("    def _plan(self")]
    at = [step_body.index(f'spans.phase("engine", "{phase}")')
          for phase in ("admit", "build_batch", "dispatch", "fetch",
                        "commit")]
    assert at == sorted(at)
    assert all(step_body.count(f'spans.phase("engine", "{phase}")') == 1
               for phase in ("admit", "build_batch", "dispatch", "fetch",
                             "commit"))
    assert src.count('spans.phase("engine"') == 5
    records = [(pl, k) for _, _, pl, k in _call_sites()
               if (pl, k) == ("engine", "step")]
    assert len(records) == 1 and "ahead=ahead" in step_body
    # the step in flight: set by the loop alone (None at construction)
    assert src.count("self._flight = ") == 1 == step_body.count(
        "self._flight = ")
    assert step_body.count("self._proposer is None") == 1
    assert "environ" not in src and "getenv" not in src
    init, = [f for c in ast.parse(src).body
             if isinstance(c, ast.ClassDef) and c.name == "InferenceEngine"
             for f in c.body
             if isinstance(f, ast.FunctionDef) and f.name == "__init__"]
    assert [a.arg for a in init.args.kwonlyargs] == [
        "max_lanes", "block_size", "num_blocks", "max_seq_len",
        "prefill_chunk", "prefill_lanes", "seed", "prefix_cache",
        "auto_start", "spec_k", "draft_proposer", "spec_adaptive", "kv_tier",
        "capture_logp"]


def test_latent_prefill_and_share_counters_are_host_sums_in_build_batch():
    """`stats()["latent"]`, `stats()["paged"]` (PR 32: the T=1 steps over a
    K/V cache, their context tokens and the runs of the decode kernel that
    held context), `stats()["prefill"]` and a share's total
    `assignments` are sums the host makes while it builds a batch: no
    transfer, no device array and no ring event of their own.  The latent
    kernel keeps the name the benchmark's readers find it by."""
    src = (PKG / "inference" / "engine.py").read_text()
    build = src[src.index("    def _build_batch("):
                src.index("    def _run_step(")]
    for counter in ("self._prefill", "self._latent", "self._paged",
                    "self._eva", "self._tokens_run"):
        sites = [m.start() for m in re.finditer(re.escape(counter) + r"\b",
                                                src)]
        writes = [m.start() for m in re.finditer(
            re.escape(counter) + r'(?:\["\w+"\])? \+?= ', src)]
        assert sites and writes, counter
        # written where they are made (the constructor) and in
        # `_build_batch`, read by `stats()` and `_moe_stats` alone
        inside = src.index("    def _build_batch(")
        assert all(w < src.index("    def submit(") or inside <= w
                   < inside + len(build) for w in writes), counter
    body = build[build.index('"""', build.index('"""') + 3):]
    assert "events.record" not in body and "spans." not in body
    assert "np.asarray(self." not in body          # no fetch from the device
    # PR 38: the table rows the prefilling lanes' last valid rows attend
    # over (what the tiled T > 1 attention reads), summed where
    # `rows_valid` is and nowhere else
    assert src.count('"ctx_rows"') == 2
    assert body.count('pf["ctx_rows"] += ') == 1
    assert body.index('pf["rows_valid"] += ') < body.index(
        'pf["ctx_rows"] += ') < body.index("elif t == 1 and live:")
    # PR 53: iterations, programs and the pair's rows are counted where a
    # plan is made and dispatched, host integers like the others
    writes = [m.start() for m in re.finditer(
        r'(?:self\._programs|mixed)\["\w+"\] \+= ', src)]
    assert len(writes) == 5
    assert all(src.index("    def step(self)") < w
               < src.index("    def _snapshot_due(") for w in writes)
    assert src.count("self._programs") == 5     # made, read by `stats()`
    names = re.findall(r'name="(\w+)"',
                       (PKG / "ops" / "attention.py").read_text())
    assert "latent_decode_attention" in names
    # A windowed cache's counters are read where the others are, its
    # compaction is dispatched outside `_build_batch` (under a span of its
    # own) and closes windows on the host: no fetch from the device.
    assert 'self._eva["compactions"]' not in src
    close = src[src.index("    def _close_windows("):
                src.index("    def _make_compact_fn(")]
    assert close.count('spans.begin("engine", "eva_compact")') == 1
    assert "events.record" not in close and "np.asarray(self." not in close
    # one Pallas call of that name: `paged_decode_roofline` divides by
    # every kernel's calls, and two readers find this one by its name
    assert names.count("paged_decode_attention") == 1


def test_the_parts_the_clocks_and_the_timeline_add_no_transfer_and_no_event():
    """PR 35's additions to an iteration: six nested `spans.phase` parts
    under `engine.<phase>` names (never `engine/`, which the idle split and
    the flatness test read), two reads of the thread's CPU clock on either
    side of `fetch` in one iteration of four, drawn (a system call each), a
    difference of the collector's seconds and a row of host numbers a
    second.  None of it touches the device, the ring beyond
    the one `engine/step` record, or the environment."""
    src = (PKG / "inference" / "engine.py").read_text()
    parts = re.findall(r'spans\.phase\(\s*"(engine\.\w+)",\s*"(\w+)"\)', src)
    assert sorted(parts) == sorted([
        ("engine.build_batch", "windows"), ("engine.build_batch", "assemble"),
        ("engine.build_batch", "upload"), ("engine.commit", "release"),
        ("engine.commit", "lock"), ("engine.commit", "deliver"),
        # PR 41: a sliding kind's blocks go back behind a commit, into the
        # same `windows` part
        ("engine.commit", "windows")])
    assert 'spans.phase("engine/' not in src
    step_body = src[src.index("    def step(self)"):
                    src.index("    def _sums(self)")]
    assert step_body.count("time.thread_time()") == 4 == src.count(
        "time.thread_time()")
    assert step_body.count("if clocked:") == 4     # each read is a sampled one
    assert step_body.count("events.record(") == 1
    # the commit's parts sit inside the commit phase, in this order
    commit = step_body[step_body.index('spans.phase("engine", "commit")'):]
    at = [commit.index(f'spans.phase("engine.commit", "{p}")')
          for p in ("release", "lock", "deliver")]
    assert at == sorted(at) and at[-1] < commit.index('took["commit"]')
    keep = src[src.index("    def _sums(self)"):
               src.index("    def _prefill_len(")]
    hook = src[src.index("def _gc_hook("):src.index("def _metrics(")]
    for body in (keep, hook):
        for banned in ("jnp.", "jax.", "np.", "events.", "spans.", "self.cache",
                       "_last_tok", "_moe_load"):
            assert banned not in body, banned
    # the uploads are where `upload` times them, and nowhere else in a step:
    # `_upload` makes one device array of the lanes' one buffer (PR 42)
    # beside the tables' cached copy; `_build_batch`, `_run_step` and
    # `_warm_widths` (which goes through `_upload`) hand nothing over
    plan = src[src.index("    def _plan(self"):
               src.index("    def _ends_in_flight(")]
    assert plan.count("self.cache.device_tables()") == 1 == src.count(
        "self.cache.device_tables()")
    assert plan.count("jnp.asarray(") == 1 == plan.count("jnp.asarray(lanes)")
    build = src[src.index("    def _build_batch("):
                src.index("    def _run_step(")]
    run = src[src.index("    def _run_step("):src.index("    def _make_entry(")]
    for body in (build, run):
        assert "jnp." not in body and "device_put" not in body
    assert "self._upload(" in run[run.index("    def _warm_widths("):]


def test_a_population_costs_the_transfers_the_upload_counter_says(
        monkeypatch):
    """PR 42: a program's lane arrays (a pair's: both populations', since
    PR 53) are one host buffer and reach the
    device by ONE call into the runtime, in `_upload`; `_run_step` hands
    nothing over (a compact program's `rows` ride in the buffer); the block
    tables go only when one changed.  Counted under patched `jnp.asarray` /
    `jax.device_put` / `jnp.array`, beside `stats()["upload"]`."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from ray_tpu.inference import InferenceEngine, engine as engine_mod
    from ray_tpu.inference import kv_cache

    eng = InferenceEngine("axk1", "axk1-nano-share", auto_start=False,
                          max_lanes=2, prefill_chunk=8, prefill_lanes=1,
                          block_size=8)
    eng.generate(list(range(1, 20)), 12)            # every program is made
    calls = []

    def counted(fn):
        def call(x, *a, **kw):
            if isinstance(x, np.ndarray) or (
                    isinstance(x, (tuple, list)) and x
                    and isinstance(x[0], np.ndarray)):
                calls.append((where[0], np.asarray(x).nbytes))
            return fn(x, *a, **kw)
        return call

    where = [""]
    assert engine_mod.jnp is jnp is kv_cache.jnp and engine_mod.jax is jax
    for mod, name in ((jnp, "asarray"), (jnp, "array"), (jax, "device_put")):
        monkeypatch.setattr(mod, name, counted(getattr(mod, name)))
    upload, run_step = eng._upload, eng._run_step

    def tagged(name, fn):
        def call(*a, **kw):
            where[0] = name
            try:
                return fn(*a, **kw)
            finally:
                where[0] = ""
        return call

    eng._upload = tagged("upload", upload)
    eng._run_step = tagged("run_step", run_step)

    def program(arrays):
        up0, n0 = dict(eng.stats()["upload"]), len(calls)
        eng._run_step(eng._upload(arrays))
        up1 = eng.stats()["upload"]
        grew = {k: up1[k] - up0[k] for k in up1}
        new = calls[n0:]
        assert grew["populations"] == 1
        assert grew["transfers"] == len(new)
        assert grew["bytes"] == sum(b for _, b in new)
        assert all(w == "upload" for w, _ in new)   # none in `_run_step`
        return arrays, len(new)

    def population(live, t):
        n0 = len(calls)
        arrays, _ = eng._build_batch(live, t)
        assert len(calls) == n0                     # assembling uploads nothing
        return program(arrays)

    # a T=1 population with unchanged tables: exactly one transfer
    eng.cache.device_tables()
    (t, _, lanes, _, rows), n = population([], 1)
    assert n == 1 and rows is None and lanes.shape == (2, 3 * 1 + 5)
    # a changed table is a second one, once
    eng.cache._dev_tables = None
    assert population([], 1)[1] == 2
    assert population([], 1)[1] == 1
    # a pair's two populations (nobody's, here): ONE, the decoding lanes'
    # [2, 8] and the chunk's compact [1, 30] in one flat buffer, the
    # chunk's `rows` in it
    flat, _, (chunk, _, rows) = engine_mod._pair_views(eng.max_lanes, 1, 8)
    _, n = program((8, False, flat, None, rows))
    assert n == 1 and flat.shape == (2 * 8 + 1 * (3 * 8 + 6),)
    assert chunk.base is flat and (rows == eng.max_lanes).all()
    # and so through the loop: a request's populations, one to two each
    up0, n0 = dict(eng.stats()["upload"]), len(calls)
    eng._upload, eng._run_step = upload, run_step
    eng.generate(list(range(2, 25)), 10)
    up1 = eng.stats()["upload"]
    pops = up1["populations"] - up0["populations"]
    assert pops == 3 + 9            # 3 chunks (the last samples), 9 T=1
    assert len(calls) - n0 == up1["transfers"] - up0["transfers"]
    assert pops <= len(calls) - n0 <= 2 * pops
    assert up1["bytes"] - up0["bytes"] == sum(b for _, b in calls[n0:])
