"""Client-side embedding cache (C++ LRU/LFU cores).

Rebuild of the reference's HET-paper embedding caches (reference:
hetu/v1/src/hetu_cache — LRU/LFU caches serving hot embedding rows locally,
pulling cold rows from the parameter server; v1/python PS ops
ParameterServerCommunicate.py).

TPU-era shape: big embedding tables live OFF-chip (host store / the
coordination KV, reference kv_store), the worker keeps a host cache of hot
rows (C++ cores: csrc/lru_cache.cpp recency eviction, csrc/lfu_cache.cpp
frequency eviction with LRU tie-break — pick per workload skew via
policy=) and device-puts only the rows a batch touches.  fetch_fn supplies
missing rows (e.g. from hetu_tpu.rpc's KV store or a memory-mapped table
file).
"""
from __future__ import annotations

import ctypes
from typing import Callable, Optional, Tuple

import numpy as np

from hetu_tpu.utils.native import load_native_lib

_LIBS = {}


def _lib(policy: str = "lru"):
    if policy in _LIBS:
        return _LIBS[policy]
    name = f"lib{policy}_cache.so"
    lib = load_native_lib(name)
    for fn, res, args in (
            (f"{policy}_create", ctypes.c_void_p, [ctypes.c_int64]),
            (f"{policy}_destroy", None, [ctypes.c_void_p]),
            (f"{policy}_lookup", None, [
                ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64),
                ctypes.c_int64, ctypes.POINTER(ctypes.c_int64),
                ctypes.POINTER(ctypes.c_int8),
                ctypes.POINTER(ctypes.c_int64)]),
            (f"{policy}_stats", None, [ctypes.c_void_p,
                                       ctypes.POINTER(ctypes.c_int64)])):
        f = getattr(lib, fn)
        f.restype = res
        f.argtypes = args
    _LIBS[policy] = lib
    return lib


class EmbeddingCache:
    """Host cache of embedding rows backed by a C++ core (LRU or LFU)."""

    def __init__(self, capacity: int, dim: int,
                 fetch_fn: Callable[[np.ndarray], np.ndarray],
                 flush_fn: Optional[Callable[[np.ndarray, np.ndarray], None]] = None,
                 dtype=np.float32, policy: str = "lru"):
        """flush_fn(ids, rows): called with DIRTY rows (updated via
        write_back) when they are evicted, so updates reach the backing
        store before the slot is reused (reference: PS push on eviction).
        policy: "lru" (recency) | "lfu" (frequency, LRU tie-break — the
        HET lfu_cache.h variant for power-law id streams)."""
        if capacity < 1:
            raise ValueError(f"capacity must be >= 1, got {capacity}")
        if policy not in ("lru", "lfu"):
            raise ValueError(f"policy must be lru|lfu, got {policy!r}")
        self.policy = policy
        self._lib = _lib(policy)
        self._create = getattr(self._lib, f"{policy}_create")
        self._destroy = getattr(self._lib, f"{policy}_destroy")
        self._lookup = getattr(self._lib, f"{policy}_lookup")
        self._stats = getattr(self._lib, f"{policy}_stats")
        self._h = self._create(capacity)
        self.capacity = capacity
        self.dim = dim
        self.fetch_fn = fetch_fn
        self.flush_fn = flush_fn
        self.buffer = np.zeros((capacity, dim), dtype)
        self._dirty: set = set()
        # id -> slot shadow map for pre-eviction row recovery
        self._slot_of: dict = {}

    def __del__(self):
        try:
            self._destroy(self._h)
        except Exception:
            pass

    def _raw_lookup(self, ids: np.ndarray):
        n = len(ids)
        slots = np.zeros(n, np.int64)
        hit = np.zeros(n, np.int8)
        evicted = np.zeros(n, np.int64)
        self._lookup(
            self._h, ids.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)), n,
            slots.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)),
            hit.ctypes.data_as(ctypes.POINTER(ctypes.c_int8)),
            evicted.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        # flush dirty evicted rows BEFORE their slots are rewritten
        ev = [int(e) for e in evicted if e >= 0]
        dirty_ev = [e for e in ev if e in self._dirty]
        if dirty_ev:
            rows = np.stack([self.buffer[self._slot_of[e]] for e in dirty_ev])
            if self.flush_fn is not None:
                self.flush_fn(np.asarray(dirty_ev, np.int64), rows)
            self._dirty.difference_update(dirty_ev)
        for e in ev:
            self._slot_of.pop(e, None)
        for i in range(n):
            self._slot_of[int(ids[i])] = int(slots[i])
        return slots, hit

    def lookup(self, ids: np.ndarray) -> np.ndarray:
        """Embedding rows for `ids` [n] -> [n, dim]; misses fetched via
        fetch_fn and installed (reference: embedding pull handler
        PSFhandle_embedding.cc)."""
        ids = np.ascontiguousarray(ids.reshape(-1), np.int64)
        slots, hit = self._raw_lookup(ids)
        miss_mask = hit == 0
        # gather resident rows BEFORE installing misses; then resolve by ID
        # every position whose id was fetched this batch — intra-batch slot
        # reuse (eviction) and same-batch hit-after-miss both make a naive
        # post-install buffer[slots] gather wrong
        out = self.buffer[slots].copy()
        if miss_mask.any():
            miss_ids = np.unique(ids[miss_mask])
            rows = np.asarray(self.fetch_fn(miss_ids), self.buffer.dtype)
            touched = np.isin(ids, miss_ids)
            out[touched] = rows[np.searchsorted(miss_ids, ids[touched])]
            # install in batch order: numpy fancy assignment keeps the LAST
            # write per duplicate slot, matching the C++ assignment order
            self.buffer[slots[miss_mask]] = rows[
                np.searchsorted(miss_ids, ids[miss_mask])]
        return out

    def write_back(self, ids: np.ndarray, rows: np.ndarray):
        """Update cached rows in place (e.g. after an embedding grad step).
        No store round-trip: slots are assigned directly and the caller's
        rows installed; rows are marked dirty and flushed to flush_fn on
        eviction."""
        ids = np.ascontiguousarray(ids.reshape(-1), np.int64)
        slots, _hit = self._raw_lookup(ids)
        self.buffer[slots] = np.asarray(rows, self.buffer.dtype)
        self._dirty.update(int(i) for i in ids)

    def flush_dirty(self):
        """Push every dirty resident row to flush_fn (checkpoint-time sync;
        eviction handles steady-state write-back)."""
        if not self._dirty or self.flush_fn is None:
            self._dirty.clear()
            return
        ids = sorted(i for i in self._dirty if i in self._slot_of)
        if ids:
            rows = np.stack([self.buffer[self._slot_of[i]] for i in ids])
            self.flush_fn(np.asarray(ids, np.int64), rows)
        self._dirty.clear()

    def stats(self) -> dict:
        out = np.zeros(4, np.int64)
        self._stats(self._h,
                            out.ctypes.data_as(ctypes.POINTER(ctypes.c_int64)))
        return {"hits": int(out[0]), "misses": int(out[1]),
                "evictions": int(out[2]), "resident": int(out[3])}


def ps_backed_cache(client, name: str, rows: int, dim: int, capacity: int,
                    init: str = "normal", scale: float = 0.02,
                    seed: int = 0, dtype=np.float32,
                    policy: str = "lru") -> "EmbeddingCache":
    """EmbeddingCache backed by the coordination server's PS tables — the
    full HET shape: server-resident table (reference: v1 ps-lite server),
    client LRU/LFU of hot rows, write-back on eviction (reference:
    hetu/v1/src/hetu_cache).  `client` is a rpc.CoordinationClient."""
    r = client.ps_init(name, rows, dim, init=init, scale=scale, seed=seed)
    if r["dim"] != dim or r["rows"] != rows:
        raise ValueError(
            f"PS table {name!r} exists with shape ({r['rows']}, {r['dim']})"
            f" != requested ({rows}, {dim})")
    return EmbeddingCache(
        capacity, dim,
        fetch_fn=lambda ids: client.ps_pull(name, ids),
        flush_fn=lambda ids, vals: client.ps_push(name, ids, vals,
                                                  mode="assign"),
        dtype=dtype, policy=policy)
