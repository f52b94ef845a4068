"""Headline benchmark: GPT-2-125M train-step throughput on one TPU chip.

Prints ONE JSON line: {"metric", "value", "unit", "vs_baseline"}.

Baseline derivation (the reference publishes no model-throughput numbers —
BASELINE.json "published" is empty): the north star is >=90% of Ray-on-
A100+NCCL throughput.  An A100 fine-tuning GPT-2-125M in bf16 at a strong
40% MFU does 0.4 * 312e12 / (6 * 124e6) ~= 168k tokens/s/chip; 90% of that
= 151k tokens/s is the bar `vs_baseline` is normalised against, scaled by
the ratio of this chip's peak bf16 FLOPs to A100's so the number is
hardware-comparable.
"""

from __future__ import annotations

import json
import time


# Peak bf16 FLOP/s per chip, by substring of device_kind (Google Cloud
# TPU documentation).  A device not listed here is an error, not a default.
PEAK_BF16_FLOPS = {"v5 lite": 197e12, "v5e": 197e12, "v5p": 459e12,
                   "v4": 275e12, "v6": 918e12}


def main():
    from ray_tpu._private import compile_cache
    compile_cache.place()

    import jax
    import optax

    from ray_tpu.models import gpt

    device = jax.devices()[0]
    if device.platform != "tpu":
        raise SystemExit(
            f"bench.py measures a TPU chip; jax found platform "
            f"{device.platform!r}")
    kind = device.device_kind.lower()
    peaks = [v for k, v in PEAK_BF16_FLOPS.items() if k in kind]
    if not peaks:
        raise SystemExit(f"no peak FLOP/s known for device_kind "
                         f"{device.device_kind!r}")
    peak = peaks[0]

    cfg = gpt.CONFIGS["gpt2-small"]
    batch, seq = 24, 1024    # b24 fastest per-token after the block/chunk
                             # retune (PERF.md round-2 sweep)

    init_state, train_step = gpt.make_train_step(cfg, optax.adamw(1e-4))
    state = init_state(jax.random.key(0))
    tokens = jax.random.randint(jax.random.key(1), (batch, seq), 0,
                                cfg.vocab_size)
    step = jax.jit(train_step, donate_argnums=0)

    # Warmup (compile) then steady-state timing; each window ends in a
    # host fetch of the loss, which cannot return before the step has run.
    for _ in range(2):
        state, metrics = step(state, {"tokens": tokens})
    float(metrics["loss"])

    n_steps = 10
    t0 = time.perf_counter()
    for _ in range(n_steps):
        state, metrics = step(state, {"tokens": tokens})
    float(metrics["loss"])
    dt = time.perf_counter() - t0

    tokens_per_sec = batch * seq * n_steps / dt

    a100_bar = 0.9 * 0.4 * 312e12 / (6 * gpt.num_params(cfg))
    bar = a100_bar * (peak / 312e12)

    print(json.dumps({
        "metric": "gpt2_125m_train_tokens_per_sec_per_chip",
        "value": round(tokens_per_sec, 1),
        "unit": "tokens/s",
        "vs_baseline": round(tokens_per_sec / bar, 3),
        "platform": device.platform,
        "device_kind": device.device_kind,
        "n_devices": len(jax.devices()),
    }))


if __name__ == "__main__":
    main()
