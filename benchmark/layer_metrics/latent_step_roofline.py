"""The traced slice's device busy time against the bytes its steps must
read, over the chip's bandwidth: per (expert layer, step) pair attention,
router, shared expert and three matrices of every held expert hit (the
window's average from `stats()["moe"]`), per step the leading dense layers
and the output head, per T=1 step the latent rows of the live context
(`stats()["latent"]`).  Pairs are counted from the trace
(`moe_grouped_matmul` calls / 3), T=1 steps from the latent kernel's calls.
A decode step is bound by these bytes; the prefill chunks in the slice are
not, and read lower."""

from __future__ import annotations

from benchmark import latent_flops, manifest


def read(run: dict):
    t = run.get("trace") or {}
    kernels = t.get("kernels") or {}
    grouped = kernels.get("moe_grouped_matmul")
    latent = kernels.get("latent_decode_attention")
    load = latent_flops.held_load(run)
    ctx = latent_flops.ctx_tokens_per_step(run)
    if not grouped or not latent or not t.get("busy_s") or load is None \
            or ctx is None:
        return None
    f = run["fields"]
    _, hit, pairs_w = load
    lead = f["first_dense_layers"]
    pairs = grouped["calls"] / 3
    steps = pairs / (f["n_layers"] - lead)
    decode_steps = latent["calls"] / f["n_layers"]
    nbytes = (pairs * latent_flops.expert_layer_weight_bytes(f, hit / pairs_w)
              + steps * (lead * latent_flops.dense_layer_weight_bytes(f)
                         + latent_flops.head_bytes(f))
              + decode_steps * latent_flops.cache_bytes(f, ctx))
    bandwidth = manifest.peaks(run["device"]["kind"])["hbm_bytes_per_s"]
    return 100.0 * nbytes / bandwidth / t["busy_s"]
