"""Tiered spill cache for refcount-0 sealed KV blocks.

Attached to a ``PagedKVCache`` (``cache.attach_tier``), this catches
blocks the allocator would otherwise destroy under pressure and keeps
their content reachable in SPILLED state:

  device pool ──evict──▶ host tier (numpy, LRU, bounded blocks)
                           │ overflow
                           ▼
                         store tier (object store when a worker context
                         exists — the hostd spill manager then handles
                         memory pressure for free — else spill files on
                         disk; LRU, bounded blocks)
                           │ overflow
                           ▼
                         dropped for real (the only lossy edge)

``match/adopt`` restores spilled chains on hit, so the effective prefix
cache is as large as host memory + the cluster object store instead of
the device pool.  All methods run under the owning engine's lock — the
tier itself is deliberately lock-free.
"""

from __future__ import annotations

import collections
import itertools
import os
import pickle
import tempfile
from typing import Dict, List, Optional, Tuple

import numpy as np

from ray_tpu.util import events
from ray_tpu.util.metrics import Counter

_MET = None


def _metrics() -> dict:
    global _MET
    if _MET is None:
        _MET = {
            "spilled": Counter(
                "kv_tier_spilled_blocks",
                "Sealed KV blocks spilled out of the device pool"),
            "restored": Counter(
                "kv_tier_restored_blocks",
                "Spilled KV blocks restored into the device pool on a "
                "prefix hit"),
            "dropped": Counter(
                "kv_tier_dropped_blocks",
                "Spilled KV blocks dropped off the end of the last tier"),
        }
    return _MET


class KVTierCache:
    """Two LRU tiers keyed by the prefix index's content-addressed chain
    key ``(parent_hash, block_tokens)``.  Values are the block's K/V
    contents ``[n_layers, block_size, kv_heads, head_dim]`` per array —
    bit-exact round-trips are the whole point, so nothing is ever
    quantized or truncated."""

    def __init__(self, host_blocks: int = 256, store_blocks: int = 1024,
                 spill_dir: Optional[str] = None):
        self.host_blocks = max(int(host_blocks), 1)
        self.store_blocks = max(int(store_blocks), 0)
        self._host: "collections.OrderedDict[Tuple, Tuple]" = \
            collections.OrderedDict()          # key -> (k_np, v_np)
        self._store: "collections.OrderedDict[Tuple, Tuple]" = \
            collections.OrderedDict()          # key -> ("ref"|"file", handle)
        self._dir = spill_dir
        self._seq = itertools.count()
        self.counters = {"kv_tier_spilled_blocks": 0,
                         "kv_tier_restored_blocks": 0,
                         "kv_tier_dropped_blocks": 0}

    @classmethod
    def from_config(cls) -> "KVTierCache":
        from ray_tpu._private.config import GLOBAL_CONFIG as cfg
        return cls(host_blocks=cfg.kv_tier_host_blocks,
                   store_blocks=cfg.kv_tier_store_blocks)

    # ---------------- public surface (cache-facing) ----------------

    def __len__(self) -> int:
        return len(self._host) + len(self._store)

    def contains(self, key) -> bool:
        return key in self._host or key in self._store

    def put(self, key, k_np: np.ndarray, v_np: np.ndarray) -> None:
        """Spill one evicted block.  Newest entries win tier capacity;
        the overflow cascades host → store → dropped."""
        if self.contains(key):
            self._touch(key)
            return
        # `v_np` None: a latent cache's block is one pool's rows.
        self._host[key] = (np.asarray(k_np),
                           None if v_np is None else np.asarray(v_np))
        self.counters["kv_tier_spilled_blocks"] += 1
        _metrics()["spilled"].inc()
        events.record("kv", "spilled", host=len(self._host),
                      store=len(self._store))
        while len(self._host) > self.host_blocks:
            old_key, (ko, vo) = self._host.popitem(last=False)
            self._demote(old_key, ko, vo)

    def pop(self, key) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        """Restore hit: hand the block's contents back (removing them —
        the caller re-indexes a device copy) or None if the key aged
        out since it was matched."""
        pair = self._host.pop(key, None)
        if pair is None:
            pair = self._store_pop(key)
        if pair is None:
            return None
        self.counters["kv_tier_restored_blocks"] += 1
        _metrics()["restored"].inc()
        events.record("kv", "restored", host=len(self._host),
                      store=len(self._store))
        return pair

    def discard(self, key) -> None:
        """The device index re-sealed identical content: the spilled
        copy is stale freight, not a drop worth counting."""
        if self._host.pop(key, None) is not None:
            return
        handle = self._store.pop(key, None)
        if handle is not None:
            self._release(handle)

    def summary_hashes(self) -> List[int]:
        """Cumulative chain hash of every spilled link, oldest first
        (mirrors the device index's seal-order summary)."""
        return [hash(k) for k in
                itertools.chain(self._store, self._host)]

    # ---------------- internals ----------------

    def _touch(self, key) -> None:
        if key in self._host:
            self._host.move_to_end(key)
        elif key in self._store:
            self._store.move_to_end(key)

    def _demote(self, key, k_np, v_np) -> None:
        handle = self._store_put((k_np, v_np)) if self.store_blocks else None
        if handle is None:
            self._drop(1)
            return
        self._store[key] = handle
        while len(self._store) > self.store_blocks:
            _k, h = self._store.popitem(last=False)
            self._release(h)
            self._drop(1)

    def _drop(self, n: int) -> None:
        self.counters["kv_tier_dropped_blocks"] += n
        _metrics()["dropped"].inc(n)
        events.record("kv", "dropped", host=len(self._host),
                      store=len(self._store))

    def _store_put(self, pair) -> Optional[Tuple[str, object]]:
        """Second tier: the object store when this process has a worker
        context (holding the ObjectRef keeps the shm object alive, and
        the hostd spill manager moves it to disk under store pressure —
        exactly the machinery this tier wants to reuse), else a spill
        file on disk.  None means no second tier is available."""
        blob = pickle.dumps(pair, protocol=pickle.HIGHEST_PROTOCOL)
        try:
            import ray_tpu
            if ray_tpu.is_initialized():
                return ("ref", ray_tpu.put(blob))
        except Exception:
            pass
        try:
            if self._dir is None:
                self._dir = tempfile.mkdtemp(prefix="ray_tpu_kv_tier_")
            path = os.path.join(self._dir, f"kv-{next(self._seq)}.bin")
            with open(path, "wb") as f:
                f.write(blob)
            return ("file", path)
        except OSError:
            return None

    def _store_pop(self, key) -> Optional[Tuple[np.ndarray, np.ndarray]]:
        handle = self._store.pop(key, None)
        if handle is None:
            return None
        kind, h = handle
        try:
            if kind == "ref":
                import ray_tpu
                blob = ray_tpu.get(h, timeout=5.0)
            else:
                with open(h, "rb") as f:
                    blob = f.read()
                os.unlink(h)
            return pickle.loads(blob)
        except Exception:
            return None         # store outage == cache miss, never an error

    def _release(self, handle) -> None:
        kind, h = handle
        if kind == "file":
            try:
                os.unlink(h)
            except OSError:
                pass
        # "ref": dropping the ObjectRef releases the store object.
