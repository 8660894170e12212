"""The one general traffic generator.  A traffic mix is a data file under
`benchmarks/traffic/`; this module turns it and a seed into work.

Every seed gives the SAME multiset of work in another order: the file
holds the lists of prompt and output lengths, and the arrival gaps are the
quantiles of the exponential distribution at the file's rate.  Requests
come in blocks of `len(prompt_lens)`; each block is cut into `strata`
sub-blocks that always hold the same lengths and gaps, and the seed
permutes inside a sub-block (order, pairing of prompt with output, token
ids).  So two seeds differ as two days of the same traffic do, not as two
deployments.

An open loop's blocks are laid on the clock so that one begins exactly at
the start of the window: block b's first request is due at b x n / rate,
and the ramp before the window is the tail of the block before.  A window
of a whole number of blocks (`chat-open`: 2 x 64 requests in 50 s) then
holds the same requests for every seed; only their order differs.

`hetu_tpu/serving/traces.py` is not used: its `poisson_arrivals` is sound
but draws the gaps anew per seed, and its `synthetic_requests` the lengths;
`exponential_gap_quantiles` below is the same process with the sampling
noise of a run taken out.
"""
from __future__ import annotations

import json
import math
import os
from typing import Iterator, List, NamedTuple

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
KINDS = ("train_job", "open_loop", "closed_loop")


def load_json(subdir: str, name: str) -> dict:
    path = os.path.join(HERE, subdir, name + ".json")
    with open(path) as f:
        return json.load(f)


def load_traffic(name: str) -> dict:
    t = load_json("traffic", name)
    if t.get("kind") not in KINDS:
        raise ValueError(f"traffic {name!r}: kind {t.get('kind')!r} is not "
                         f"one of {KINDS}")
    return t


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """Independent generator per (seed, purpose); any non-negative seed,
    however large."""
    return np.random.default_rng([int(seed), sum(stream.encode())])


def jax_seed(seed: int) -> int:
    """A seed JAX's 32-bit key constructor takes, from any `--seed`."""
    return int(rng_for(seed, "jax").integers(0, 2 ** 31 - 1))


# ---------------------------------------------------------------------------
# fixed-multiset blocks
# ---------------------------------------------------------------------------

def exponential_gap_quantiles(n: int, rate_per_s: float) -> np.ndarray:
    """n gaps: the mid-point quantiles of Exp(rate), scaled so that their
    mean is exactly 1/rate.  In random order they are a Poisson stream's
    gaps with the sampling noise of the count taken out."""
    u = (np.arange(n) + 0.5) / n
    g = -np.log1p(-u)
    return g / g.mean() / rate_per_s


def _strata_permutation(n: int, strata: int, rng) -> np.ndarray:
    """Order of one block's n items: sub-block j holds the items
    j, j+strata, j+2*strata, ... (every sub-block spans the whole range of
    a sorted list) and the seed permutes inside each sub-block."""
    out = []
    for j in range(strata):
        idx = np.arange(j, n, strata)
        out.append(rng.permutation(idx))
    return np.concatenate(out)


class PlannedRequest(NamedTuple):
    rid: int
    due: float            # seconds from the start of the window (< 0: ramp)
    prompt: np.ndarray    # int32 token ids
    max_new: int


def plan_requests(traffic: dict, seed: int, vocab_size: int, *,
                  count: int = 0, ramp_s: float = 0.0, until_s: float = 0.0
                  ) -> List[PlannedRequest]:
    """The mix's requests in order of `due`.  A closed loop: `count`
    requests, all due at 0 (the client sends one when one completes).  An
    open loop: every request due in [-ramp_s, until_s), from whole blocks
    that start at the last block boundary at or before `-ramp_s`; request
    k of block b is due at b x n / rate plus the block's first k gaps, so
    a block's first request is due exactly on the boundary."""
    prompts = np.asarray(traffic["prompt_lens"], np.int64)
    outputs = np.asarray(traffic["output_lens"], np.int64)
    n = len(prompts)
    if len(outputs) != n:
        raise ValueError("prompt_lens and output_lens differ in length")
    strata = int(traffic.get("strata", 1))
    open_loop = traffic["kind"] == "open_loop"
    if open_loop:
        rate = float(traffic["rate_per_s"])
        gaps = exponential_gap_quantiles(n, rate)
        block_s = n / rate
        blocks = range(-math.ceil(ramp_s / block_s),
                       math.ceil(until_s / block_s))
    else:
        blocks = range(math.ceil(count / n))
    rng = rng_for(seed, "plan")
    out: List[PlannedRequest] = []
    for b in blocks:
        p_order = _strata_permutation(n, strata, rng)
        o_order = _strata_permutation(n, strata, rng)
        due = np.zeros(n)
        if open_loop:
            g_block = gaps[_strata_permutation(n, strata, rng)]
            due = b * block_s + np.concatenate([[0.0],
                                                np.cumsum(g_block)[:-1]])
        for k in range(n):
            plen, olen = int(prompts[p_order[k]]), int(outputs[o_order[k]])
            ids = rng.integers(0, vocab_size, size=plen, dtype=np.int32)
            keep = (-ramp_s <= due[k] < until_s if open_loop
                    else len(out) < count)
            if keep:
                out.append(PlannedRequest((b - blocks[0]) * n + k,
                                          float(due[k]), ids, olen))
    return out


# ---------------------------------------------------------------------------
# training batches
# ---------------------------------------------------------------------------

def train_batches(traffic: dict, seed: int, vocab_size: int
                  ) -> Iterator[dict]:
    """A fresh seeded batch every step: uniform ids, every position real
    (packed, no padding), labels = inputs (the model shifts)."""
    rng = rng_for(seed, "batches")
    shape = (int(traffic["global_batch"]), int(traffic["seq_len"]))
    while True:
        ids = rng.integers(0, vocab_size, size=shape, dtype=np.int32)
        yield {"input_ids": ids, "labels": ids}


# ---------------------------------------------------------------------------
# window arithmetic (kept with the generator: both define "the work")
# ---------------------------------------------------------------------------

def step_intervals(completions: List[float]) -> dict:
    """From the times at which successive steps completed (the first
    interval, which follows warm-up, dropped by the caller): `rate_hz`,
    all the steps over all the time from the first completion to the
    last; the intervals and their median; and `stall_pct`, the share of
    that time which steps x median does not explain (one stalled step in
    a hundred, or a stall of every n-th step, shows here and in the
    rate, not in the median)."""
    iv = np.diff(np.asarray(completions, np.float64))
    if len(iv) < 1:
        raise ValueError("need at least two completions")
    med = float(np.median(iv))
    span = float(completions[-1] - completions[0])
    return {"intervals": iv.tolist(), "median_s": med, "span_s": span,
            "rate_hz": len(iv) / span,
            "stall_pct": 100.0 * max(0.0, 1.0 - len(iv) * med / span)}


def slice_rates(step_ends: List[float], step_tokens: List[int],
                t_start: float, slice_steps: int) -> dict:
    """Throughput of a window of whole engine steps that begins at
    `t_start` (the end of the step before the first).  `rate` is all the
    tokens over all the time.  The steps are also cut into consecutive
    slices of `slice_steps`; `stall_pct` is the share of the sliced span
    that slices x median slice duration does not explain, and the slices'
    own rates go to the readings file.  (The median of the slice rates
    was tried as the metric and is NOT used: the work in a slice varies by
    a fifth with the mix of prefill and decode in flight, so the median
    of ~55 slices spread 3-5% between runs where the whole window's rate
    spread 1.1%; PERF.md s6.)"""
    ends = np.asarray(step_ends, np.float64)
    toks = np.asarray(step_tokens, np.float64)
    n = len(ends) // slice_steps
    if n < 1:
        raise ValueError(f"{len(ends)} steps do not fill one slice of "
                         f"{slice_steps}")
    edges = np.concatenate([[t_start],
                            ends[slice_steps - 1: n * slice_steps: slice_steps]])
    dur = np.diff(edges)
    tok = toks[: n * slice_steps].reshape(n, slice_steps).sum(axis=1)
    span = float(edges[-1] - edges[0])
    return {"rate": float(toks.sum() / (ends[-1] - t_start)),
            "slice_tokens": tok.tolist(), "slice_s": dur.tolist(),
            "slice_rates": (tok / dur).tolist(),
            "stall_pct": 100.0 * max(
                0.0, 1.0 - n * float(np.median(dur)) / span)}


def percentile(values, p: float) -> float:
    """Linear-interpolation percentile (numpy's default), of all values."""
    if len(values) == 0:
        raise ValueError("no values")
    return float(np.percentile(np.asarray(values, np.float64), p))
