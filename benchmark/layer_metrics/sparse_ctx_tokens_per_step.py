"""Context tokens a T=1 step scored, all lanes together, inside the window:
`stats()["sparse"]` (`ctx_tokens` over `decode_steps`), read at the window's
two ends."""

from __future__ import annotations

from benchmark import sparse_flops


def read(run: dict):
    per = sparse_flops.per_step(run)
    return per[0] if per else None
