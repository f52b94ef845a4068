"""Context tokens a T=1 step attended over, all lanes together, inside the
window: `stats()["latent"]` (`ctx_tokens` over `decode_steps`), read at the
window's two ends."""

from benchmark.latent_flops import ctx_tokens_per_step as read  # noqa: F401
