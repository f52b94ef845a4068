"""Wire format for sealed KV blocks + hash-chain metadata.

One encoded payload carries a contiguous chain of sealed blocks — the
per-block token tuples (enough to rebuild every content-addressed chain
key from the root) and the gathered K/V pool contents, dtype and all.
The decode-side ``PagedKVCache.install_prefix`` adopts the blocks as if
it had sealed them itself, so a prefill→decode handoff is bit-exact by
construction and idempotent on retry (content-addressed links already
present are skipped).

The payload is bytes on the wire: beyond the inline-object threshold it
automatically rides the native shm object plane (``objtransfer.cc`` via
``object_transfer.py``) like any other big serve argument — the codec
never needs to know about transports.
"""

from __future__ import annotations

import io
import pickle
from typing import Optional

import numpy as np

_MAGIC = b"KVT1"


def _as_numpy(tree):
    """A payload's further kinds (`more`) with every array as contiguous
    numpy."""
    if isinstance(tree, dict):
        return {k: _as_numpy(v) for k, v in tree.items()}
    if isinstance(tree, (list, tuple)):
        return [_as_numpy(v) for v in tree]
    return tree if isinstance(tree, int) else np.ascontiguousarray(tree)


class KVCodecError(ValueError):
    """Payload is not a KVBlockCodec frame (or an incompatible one)."""


class KVBlockCodec:
    """Encode/decode ``PagedKVCache.export_prefix`` payloads.

    The frame is a 4-byte magic + a pickled dict whose arrays are plain
    numpy (pickle round-trips them bit-exactly, dtype included).  A
    version field inside the dict gates forward compatibility; the
    magic catches whole-payload confusion early (a truncated or foreign
    blob raises KVCodecError, never a half-installed cache)."""

    @staticmethod
    def encode(payload: dict) -> bytes:
        if not payload or payload.get("v") != 1:
            raise KVCodecError("not an export_prefix v1 payload")
        buf = io.BytesIO()
        buf.write(_MAGIC)
        pickle.dump(
            {
                "v": 1,
                # "kv": K and V rows; "state": as "kv", and under `more`
                # the snapshot of the mixers' state behind the chain, a
                # buffer under each name the cache's state part states
                # ("state" and "tail", or "tail" alone), or as "latent"
                # with the same beside it (lane state behind a latent pool);
                # "latent": one latent row in `k`, `v_pool` None; "layered":
                # as "latent" or as "kv" (a cache's kinds are all latent
                # rows or all K and V rows), and under `more` the blocks of
                # the cache's other kinds, each said to be whose
                # (inference/kv_cache.py).
                "kind": payload.get("kind", "kv"),
                **({"more": _as_numpy(payload["more"])}
                   if payload.get("more") else {}),
                "block_size": int(payload["block_size"]),
                "chain": [list(map(int, blk)) for blk in payload["chain"]],
                "k": np.ascontiguousarray(payload["k"]),
                "v_pool": (None if payload["v_pool"] is None else
                           np.ascontiguousarray(payload["v_pool"])),
            },
            buf, protocol=pickle.HIGHEST_PROTOCOL)
        return buf.getvalue()

    @staticmethod
    def decode(blob: bytes) -> dict:
        if not isinstance(blob, (bytes, bytearray, memoryview)):
            raise KVCodecError(f"expected bytes, got {type(blob).__name__}")
        blob = bytes(blob)
        if blob[:4] != _MAGIC:
            raise KVCodecError("bad magic: not a KV block frame")
        try:
            payload = pickle.loads(blob[4:])
        except Exception as exc:
            raise KVCodecError(f"corrupt KV block frame: {exc}") from exc
        if payload.get("v") != 1:
            raise KVCodecError(f"unknown KV frame version {payload.get('v')}")
        k, v = payload["k"], payload["v_pool"]
        n = len(payload["chain"])
        bs = payload["block_size"]
        # whether a frame of this kind comes without a V: "layered" and
        # "state" either
        no_v = {"latent": (True,), "layered": (True, False),
                "state": (True, False)}.get(
            payload.setdefault("kind", "kv"), (False,))
        if (v is None) not in no_v \
                or (v is not None and k.shape != v.shape) \
                or k.shape[1] != n or k.shape[2] != bs:
            raise KVCodecError(
                f"frame shape mismatch: k{k.shape} "
                f"v{None if v is None else v.shape} vs {n} chain blocks "
                f"of size {bs} of kind {payload['kind']}")
        return payload

    @staticmethod
    def try_decode(blob) -> Optional[dict]:
        """Decode-or-None: the decode path treats a bad handoff as a
        cache miss (re-prefill), never a failed request."""
        try:
            return KVBlockCodec.decode(blob)
        except KVCodecError:
            return None
