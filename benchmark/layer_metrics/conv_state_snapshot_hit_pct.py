"""Share of the window's admissions that adopted a snapshot of the conv
layers' tails behind their prompt's sealed head (and so convolved only their
own question): `stats()["ssm"]["snapshots_adopted"]` (the state part's
counter, what `ssm_snapshots_adopted_per_s` reads a second) over itself plus
`prefix_misses` (the admissions that found no block to adopt, a head whose
snapshot was gone among them), both the cache's own counts of one event an
admission, read at the window's two ends.  (Over the engine's `admitted` the
share read 100.117 in one run: 856 over 855, the cache's count a line ahead
of the engine's when the driver's thread read them.)"""

from __future__ import annotations

from benchmark import ssm_flops


def read(run: dict):
    adopted = ssm_flops.delta(run, "ssm", "snapshots_adopted")
    s0, s1 = run.get("stats0") or {}, run.get("stats1") or {}
    if adopted is None or "prefix_misses" not in s0 \
            or "prefix_misses" not in s1:
        return None
    looked = adopted + s1["prefix_misses"] - s0["prefix_misses"]
    return 100.0 * adopted / looked if looked > 0 else None
