"""ctypes binding for the C++ search core (csrc/dp_core.cpp; reference:
tools/Galvatron/csrc/dp_core.cpp bound via pybind11; ctypes here — no
pybind11 in the TPU image).  The core is built from source on first use
(utils/native.py); `_dp_python` is the plain reference the tests hold it
to, not a fallback."""
from __future__ import annotations

import ctypes
from typing import List, Sequence, Tuple

import numpy as np

from hetu_tpu.utils.native import load_native_lib

_LIB = None


def _lib() -> ctypes.CDLL:
    global _LIB
    if _LIB is None:
        lib = load_native_lib("libdp_core.so")
        lib.dynamic_programming_core.restype = ctypes.c_int
        lib.balance_stages.restype = ctypes.c_int
        _LIB = lib
    return _LIB


def dynamic_programming_core(time: Sequence[float], mem: Sequence[int],
                             trans: np.ndarray, num_layers: int,
                             budget: int) -> Tuple[List[int], float]:
    """Choose a strategy per layer minimizing total time under the memory
    budget. Returns (choices[num_layers], total_time). Raises ValueError if
    infeasible."""
    S = len(time)
    time_a = np.ascontiguousarray(time, np.float64)
    mem_a = np.ascontiguousarray(mem, np.int32)
    trans_a = np.ascontiguousarray(trans, np.float64).reshape(S * S)
    out = np.zeros(num_layers, np.int32)
    out_t = ctypes.c_double()
    rc = _lib().dynamic_programming_core(
        ctypes.c_int32(num_layers), ctypes.c_int32(S),
        time_a.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        mem_a.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        trans_a.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        ctypes.c_int32(budget),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)),
        ctypes.byref(out_t))
    if rc != 0:
        raise ValueError("no feasible strategy assignment under budget")
    return out.tolist(), out_t.value


def _dp_python(time, mem, trans, L, budget):
    INF = float("inf")
    S = len(time)
    dp = np.full((budget + 1, S), INF)
    parent = np.full((L, budget + 1, S), -1, np.int32)
    for s in range(S):
        if mem[s] <= budget:
            dp[mem[s], s] = time[s]
    for layer in range(1, L):
        nxt = np.full_like(dp, INF)
        for m in range(budget + 1):
            for s in range(S):
                cur = dp[m, s]
                if cur == INF:
                    continue
                for s2 in range(S):
                    m2 = m + mem[s2]
                    if m2 > budget:
                        continue
                    cand = cur + time[s2] + trans[s, s2]
                    if cand < nxt[m2, s2]:
                        nxt[m2, s2] = cand
                        parent[layer, m2, s2] = s
        dp = nxt
    flat = np.argmin(dp)
    bm, bs = divmod(int(flat), S)
    if dp[bm, bs] == INF:
        raise ValueError("no feasible strategy assignment under budget")
    total = float(dp[bm, bs])
    choice = [0] * L
    m, s = bm, bs
    for layer in range(L - 1, -1, -1):
        choice[layer] = s
        if layer:
            ps = int(parent[layer, m, s])
            m -= mem[s]
            s = ps
    return choice, total


def balance_stages(num_layers: int, speeds: Sequence[float]) -> List[int]:
    """Per-stage layer counts proportional to device speeds (Malleus-style
    hetero pipeline balancing; reference: engine/strategy.py StrategyModel)."""
    P = len(speeds)
    sp = np.ascontiguousarray(speeds, np.float64)
    out = np.zeros(P, np.int32)
    rc = _lib().balance_stages(
        ctypes.c_int32(num_layers), ctypes.c_int32(P),
        sp.ctypes.data_as(ctypes.POINTER(ctypes.c_double)),
        out.ctypes.data_as(ctypes.POINTER(ctypes.c_int32)))
    if rc != 0:
        raise ValueError("cannot balance stages")
    return out.tolist()
