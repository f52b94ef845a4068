"""Training batches: a fixed set of token batches drawn on the device from the
seed and cycled, so the input pipeline costs nothing and every run of one
seed trains on the same tokens.  Parameters (traffic file): `batch`, `seq`,
`n_batches`."""

from __future__ import annotations


def make(traffic: dict, seed: int, vocab_size: int) -> list:
    """`n_batches` arrays [batch, seq] int32, uniform over the vocabulary
    (runs where jax does: in the worker that holds the chip)."""
    import jax
    key = jax.random.key(seed)
    draw = jax.jit(lambda k: jax.random.randint(
        k, (traffic["batch"], traffic["seq"]), 0, vocab_size))
    return [draw(jax.random.fold_in(key, 1000 + i))
            for i in range(traffic["n_batches"])]
