"""Speculative decoding: draft k tokens on the host, verify k+1 in one
batched forward.

The decode-step cost of a serving engine is HBM-bound: every step reads
the full parameter set once no matter how many tokens it emits.
Speculative decoding amortizes that read — a cheap DRAFTER proposes k
tokens per slot, and ONE `verify_step_paged` forward
(models/generation.py — the paged decode step over a block of k+1
queries a slot) scores all k+1 positions.  Accepted drafts emit in bulk; the
roofline win is provable hardware-free (`roofline_report`, the
comm/wire.py discipline; bench.py detail.serving records it).

**Acceptance = sample-then-match.**  Per verify position the engine
computes the token the SEQUENTIAL path would have emitted there —
argmax for greedy rows, `sampling.sample_tokens` with the position's
own fold_in key for sampling rows — and accepts draft tokens while they
match.  For a DETERMINISTIC drafter (a point-mass proposal q) this is
exactly the standard speculative rejection rule: the draft is accepted
with probability p(d), and conditioned on rejection the emitted token
is distributed as the residual norm(max(p - q, 0)) = p restricted to
tokens != d — so the output DISTRIBUTION matches the non-speculative
path, and because the per-position PRNG keys are identical, sampled
output is token-IDENTICAL run-for-run too.  Greedy is the
temperature->0 case: accept iff draft == argmax (token-identical to
sequential `generate()`, the acceptance golden).

**Stochastic drafters** (``Drafter.stochastic``) expose the full
proposal DISTRIBUTION q per draft position (`propose_with_probs`), and
the engine verifies them with the full rejection rule instead
(:func:`stochastic_verify`, in-graph): draft d_i is accepted with
probability min(1, p(d_i)/q(d_i)), and the first rejected position
resamples from the residual norm(max(p - q, 0)).  The output
distribution is exactly p (the sequential path's), for ANY q — the
sample-then-match rule is the point-mass special case.  All the draws
(accept uniforms, residual Gumbels) come from the same counter-based
hash of the (seed, absolute_position) fold_in key the sampler uses
(`ops/pallas/sample.hash_uniform`, lanes 1/2), so stochastic verify is
as replay-deterministic as everything else.

**Drafters** are pluggable host-side proposers (`Drafter.propose`).
`NGramDrafter` is the built-in model-free one (prompt-lookup decoding):
match the longest recent n-gram earlier in the sequence and replay the
tokens that followed it — free to compute, and highly effective on the
repetitive spans (code, quotations, structured output) where serving
traffic actually burns tokens.  `ModelDrafter` runs a small draft MODEL
(resident-quantized, the serving/experts.py discipline) and samples its
rollout from the model's own temperature-scaled softmax — the q the
stochastic rule needs.

Gated by ``HETU_TPU_SPEC_DECODE`` (none | ngram | model; registered
identity contract — unset builds the pre-speculative decode program
byte-for-byte) with ``HETU_TPU_SPEC_K`` draft tokens per step.  See
docs/serving.md.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp


class Drafter:
    """Host-side draft proposer interface."""

    #: how many trailing context tokens `propose` actually reads; the
    #: engine slices the sequence to this before calling (None = the
    #: full history) so drafting stays O(window) per step instead of
    #: rebuilding the whole prompt+generated list on the decode hot
    #: loop (quadratic per request at long contexts)
    window: Optional[int] = None

    #: True when the drafter SAMPLES its proposals and reports the full
    #: distribution via `propose_with_probs`; the engine then verifies
    #: with the stochastic p/q rejection rule instead of
    #: sample-then-match (which stays exact only for point-mass q)
    stochastic: bool = False

    def propose(self, tokens: Sequence[int], k: int) -> List[int]:
        """Propose k draft continuations of `tokens` (the trailing
        `window` of prompt + generated so far).  Must return exactly k
        token ids."""
        raise NotImplementedError

    def propose_with_probs(self, tokens: Sequence[int], k: int, *,
                           seed: int = 0, start_pos: int = 0
                           ) -> Tuple[List[int], np.ndarray]:
        """Stochastic form: k draft tokens plus the [k, V] proposal
        distributions they were drawn from.  `seed`/`start_pos` feed the
        replay-deterministic draw (the request's sampling seed and the
        absolute position of the first drafted token).  Only drafters
        with ``stochastic = True`` implement this."""
        raise NotImplementedError(
            f"{type(self).__name__} is a deterministic drafter; the "
            "engine verifies it by sample-then-match")


class NGramDrafter(Drafter):
    """Prompt-lookup drafting: find the most recent earlier occurrence
    of the longest trailing n-gram (n down to 1) and propose the tokens
    that followed it; pad by repeating the last token when the lookup
    comes up short (a deliberately cheap tail — mismatches cost one
    rejected draft, not correctness)."""

    def __init__(self, max_ngram: int = 3, window: int = 1024):
        if max_ngram < 1:
            raise ValueError("max_ngram must be >= 1")
        self.max_ngram = max_ngram
        self.window = window

    def propose(self, tokens: Sequence[int], k: int) -> List[int]:
        toks = list(tokens[-self.window:])
        n = len(toks)
        out: List[int] = []
        for m in range(min(self.max_ngram, n - 1), 0, -1):
            tail = toks[n - m:]
            # most recent earlier occurrence of the trailing m-gram
            for s in range(n - m - 1, -1, -1):
                if toks[s:s + m] == tail:
                    out = toks[s + m: s + m + k]
                    break
            if out:
                break
        last = toks[-1] if toks else 0
        while len(out) < k:
            out.append(out[-1] if out else last)
        return out[:k]


class CallableDrafter(Drafter):
    """Adapter: any ``fn(tokens, k) -> [k] ids`` (e.g. a small draft
    model's rollout) as a Drafter."""

    def __init__(self, fn):
        self.fn = fn

    def propose(self, tokens: Sequence[int], k: int) -> List[int]:
        out = list(self.fn(tokens, k))
        if len(out) != k:
            raise ValueError(f"drafter returned {len(out)} tokens, "
                             f"wanted {k}")
        return out


def _quantize_resident(params, *, bits: int, block: int):
    """Blockwise-quantize every float matrix leaf of a params tree for
    RESIDENT storage (the serving/experts.py discipline, applied to the
    whole draft model: the int payload + f32 scales live in device
    memory; the forward dequantizes a working copy in-program).  1-D
    leaves (norm gains, biases) stay fp — they are bytes-trivial and
    precision-critical.  Returns (tree_q, spec)."""
    from hetu_tpu.comm.compress import quantize_blockwise
    spec: Dict[str, Any] = {}

    def walk(node, path):
        if isinstance(node, dict):
            return {k: walk(v, path + (k,)) for k, v in node.items()}
        leaf = node
        if getattr(leaf, "ndim", 0) < 2 or not jnp.issubdtype(
                jnp.asarray(leaf).dtype, jnp.floating):
            return leaf
        flat = jnp.asarray(leaf).reshape(-1).astype(jnp.float32)
        n = flat.shape[0]
        pad = (-n) % block
        if pad:
            flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
        q, s = quantize_blockwise(flat, block, bits=bits)
        spec["/".join(path)] = {"shape": tuple(int(d) for d in leaf.shape),
                                "dtype": jnp.asarray(leaf).dtype}
        return {"q": q, "s": s}

    return walk(params, ()), spec


def _dequantize_resident(params_q, spec):
    """In-program inverse of `_quantize_resident`."""
    from hetu_tpu.comm.compress import dequantize_blockwise

    def walk(node, path):
        if not isinstance(node, dict):
            return node
        meta = spec.get("/".join(path))
        if meta is not None:
            flat = dequantize_blockwise(node["q"], node["s"])
            n = int(np.prod(meta["shape"]))
            return flat[:n].reshape(meta["shape"]).astype(meta["dtype"])
        return {k: walk(v, path + (k,)) for k, v in node.items()}

    return walk(params_q, ())


class ModelDrafter(Drafter):
    """A small draft MODEL as a stochastic drafter.

    Proposals are SAMPLED from the draft model's temperature-scaled
    softmax — exactly the q distribution `propose_with_probs` reports,
    which is what makes the engine's stochastic p/q rejection rule
    distribution-exact for any draft model, good or bad.  The draw is
    Gumbel-argmax over the shared counter-based hash (lane 3) of the
    request's (seed, absolute_position) fold_in key, so drafts replay
    deterministically like every other sampled token.  temperature=0
    degenerates to an argmax rollout with a point-mass q (the
    deterministic rule falls out of the stochastic one).

    The draft params are blockwise-quantized at construction and live
    resident in int8 (`_quantize_resident`); each propose runs k full
    forwards over a bounded trailing window — the draft model is small
    enough that re-reading its params k times still costs a fraction of
    one target-model verify step."""

    stochastic = True

    def __init__(self, model, params, *, temperature: float = 1.0,
                 window: int = 256, quantize_bits: int = 8,
                 quantize_block: int = 64):
        if window < 1:
            raise ValueError("window must be >= 1")
        if temperature < 0:
            raise ValueError("temperature must be >= 0")
        self.model = model
        self.temperature = float(temperature)
        self.window = int(window)
        self.params_q, self._spec = _quantize_resident(
            params, bits=quantize_bits, block=quantize_block)

        def fwd(pq, ctx):
            from hetu_tpu.models import generation
            p = _dequantize_resident(pq, self._spec)
            logits, _ = generation.prefill(model, p, ctx, ctx.shape[1])
            return logits[0].astype(jnp.float32)           # [V]

        self._fwd = jax.jit(fwd)

    def propose(self, tokens: Sequence[int], k: int) -> List[int]:
        return self.propose_with_probs(tokens, k)[0]

    def propose_with_probs(self, tokens: Sequence[int], k: int, *,
                           seed: int = 0, start_pos: int = 0
                           ) -> Tuple[List[int], np.ndarray]:
        from hetu_tpu.ops.pallas.sample import gumbel
        from hetu_tpu.serving.sampling import key_words
        toks = list(tokens[-self.window:]) or [0]
        out: List[int] = []
        probs: List[np.ndarray] = []
        for i in range(k):
            ctx = jnp.asarray([toks[-self.window:]], jnp.int32)
            logits = self._fwd(self.params_q, ctx)
            if self.temperature > 0:
                scaled = logits / self.temperature
                words = key_words(jnp.asarray([seed]),
                                  jnp.asarray([start_pos + i]))
                g = gumbel(words[0, 0], words[0, 1],
                           jnp.arange(logits.shape[0], dtype=jnp.uint32),
                           lane=3)
                tok = int(jnp.argmax(scaled + g))
                q = np.asarray(jax.nn.softmax(scaled))
            else:
                tok = int(jnp.argmax(logits))
                q = np.zeros(logits.shape[0], np.float32)
                q[tok] = 1.0
            out.append(tok)
            probs.append(q)
            toks.append(tok)
        return out, np.stack(probs)


def make_drafter(mode: str, **kw) -> Optional[Drafter]:
    """The HETU_TPU_SPEC_DECODE vocabulary -> a Drafter (None for
    'none').  mode='model' requires `model` and `params` kwargs (the
    engine forwards its draft_model/draft_params)."""
    if mode == "none":
        return None
    if mode == "ngram":
        return NGramDrafter(**kw)
    if mode == "model":
        if "model" not in kw or "params" not in kw:
            raise ValueError("spec-decode mode 'model' needs a draft "
                             "model: pass model=/params= (the engine's "
                             "draft_model/draft_params kwargs)")
        return ModelDrafter(**kw)
    raise ValueError(f"unknown spec-decode mode {mode!r}; "
                     "choices: ('none', 'ngram', 'model')")


def stochastic_verify(logits_grid, q_probs, drafts, seeds, positions,
                      temps, top_ks, top_ps):
    """The full speculative rejection rule, in-graph (the stochastic
    drafters' verify epilogue; jnp, jit-safe).

    logits_grid: [S, k+1, V] target logits at the verify positions;
    q_probs: [S, k, V] the drafter's proposal distributions; drafts:
    [S, k] the proposed tokens (SAMPLED from q); positions: [S, k+1]
    absolute sequence positions of the tokens being decided (the key
    derivation input); temps/top_ks/top_ps: [S] per-slot sampling
    params.  Returns (out_tokens [S, k+1] int32, n_emit [S] int32).

    Per draft position i: the target distribution p is the softmax of
    the FILTERED temperature-scaled logits (exactly what the sequential
    sampler draws from); accept with probability min(1, p(d_i)/q(d_i))
    using a lane-1 hash uniform of the position's fold_in key; the
    first rejected position emits a residual resample from
    norm(max(p - q, 0)) via lane-2 Gumbel-argmax.  Greedy rows
    (temp == 0) collapse to accept-iff-argmax with an argmax
    correction.  Full acceptance emits the bonus token, sampled at
    position k with the position's own lane-0 key — identical to the
    sequential path's draw there."""
    from hetu_tpu.ops.pallas.sample import gumbel, hash_uniform
    from hetu_tpu.serving import sampling

    S, C, V = logits_grid.shape
    k = C - 1
    rep = lambda x: jnp.repeat(x, k)  # noqa: E731 — [S] -> [S*k]

    # target distribution p at the k draft positions: softmax of the
    # SAME filtered logits the sequential sampler argmax-Gumbels over
    filt = sampling.filtered_logits(
        logits_grid[:, :k].reshape(S * k, V), rep(temps), rep(top_ks),
        rep(top_ps)).reshape(S, k, V)
    p = jax.nn.softmax(filt, axis=-1)                          # [S, k, V]
    q = q_probs.astype(jnp.float32)

    rows = jnp.arange(S)
    d = drafts.astype(jnp.int32)
    p_d = jnp.take_along_axis(p, d[..., None], axis=-1)[..., 0]  # [S, k]
    q_d = jnp.take_along_axis(q, d[..., None], axis=-1)[..., 0]

    words = sampling.key_words(rep(seeds), positions[:, :k].reshape(-1))
    u = hash_uniform(words[:, 0], words[:, 1],
                     jnp.zeros((S * k,), jnp.uint32),
                     lane=1).reshape(S, k)
    ratio = p_d / jnp.maximum(q_d, 1e-30)
    greedy_tok = jnp.argmax(logits_grid, axis=-1).astype(jnp.int32)
    sampling_row = (temps > 0)[:, None]
    accept = jnp.where(sampling_row, u <= ratio,
                       d == greedy_tok[:, :k])                 # [S, k]

    # residual resample per draft position (only position r is used);
    # p <= q everywhere (p == q) leaves no residual -> resample from p
    res = jnp.maximum(p - q, 0.0)
    has_res = jnp.sum(res, axis=-1, keepdims=True) > 1e-9
    scores = jnp.where(
        has_res, jnp.where(res > 0, jnp.log(jnp.maximum(res, 1e-30)),
                           -1e30),
        filt)
    g = gumbel(words[:, 0:1], words[:, 1:2],
               jnp.arange(V, dtype=jnp.uint32)[None, :],
               lane=2).reshape(S, k, V)
    resample = jnp.argmax(scores + g, axis=-1).astype(jnp.int32)
    resample = jnp.where(sampling_row, resample, greedy_tok[:, :k])

    # bonus token at position k: the sequential path's own draw there
    bonus = sampling.sample_tokens(
        logits_grid[:, k], seeds, positions[:, k], temps, top_ks, top_ps)

    acc_cum = jnp.cumprod(accept.astype(jnp.int32), axis=1)
    r = jnp.sum(acc_cum, axis=1)                               # [S] in [0, k]
    n_emit = (r + 1).astype(jnp.int32)
    correction = jnp.where(
        r < k, resample[rows, jnp.clip(r, 0, k - 1)], bonus)
    out = jnp.concatenate([d, bonus[:, None]], axis=1)
    out = out.at[rows, r].set(correction)
    return out.astype(jnp.int32), n_emit


def accept_counts(targets: np.ndarray, drafts: np.ndarray) -> np.ndarray:
    """Host-side twin of the in-graph acceptance rule (the engine's
    program computes this with cumprod; tests pin the two together).
    targets: [S, k+1] the per-position sequential-path tokens; drafts:
    [S, k].  Returns [S] n_emit in [1, k+1]: the longest matched prefix
    plus the one always-emitted correction/bonus token."""
    match = targets[:, :-1] == drafts            # [S, k]
    acc = np.cumprod(match.astype(np.int64), axis=1).sum(axis=1)
    return acc + 1


# ---------------------------------------------------------------------------
# analytic roofline (bench.py detail.serving, the hardware-free pattern)
# ---------------------------------------------------------------------------

def expected_tokens_per_step(acceptance: float, k: int) -> float:
    """E[tokens emitted per verify step] under per-position acceptance
    probability `acceptance`: 1 + a + a^2 + ... + a^k (the matched
    prefix is geometric, truncated at k, plus the always-emitted
    bonus/correction token)."""
    if not 0.0 <= acceptance <= 1.0:
        raise ValueError(f"acceptance must be in [0, 1], got {acceptance}")
    if acceptance == 1.0:
        return float(k + 1)
    return (1.0 - acceptance ** (k + 1)) / (1.0 - acceptance)


def roofline_report(*, n_params: float, flops_per_token: float,
                    step_bytes: float, slots: int, k: int,
                    acceptance: float, peak_flops: float,
                    hbm_bytes_per_s: float,
                    draft_flops_per_step: float = 0.0,
                    draft_bytes_per_step: float = 0.0
                    ) -> Dict[str, float]:
    """Analytic spec-decode speedup at the roofline (hardware-free).

    A plain decode step moves `step_bytes` (params + every slot's KV)
    and computes `slots * flops_per_token`; a verify step moves the
    SAME bytes (params read once, KV read once — the k+1 queries share
    them) but computes (k+1)x the FLOPs and emits
    `expected_tokens_per_step(acceptance, k)` tokens per slot.  While
    decode is HBM-bound (it always is at serving batch sizes), the
    verify step's extra FLOPs ride under the same memory roof and the
    speedup approaches E[emit] directly.

    A MODEL drafter (HETU_TPU_SPEC_DECODE=model) is not free like the
    n-gram table: its k sequential forwards cost
    `draft_flops_per_step` / `draft_bytes_per_step` per verify step
    (the resident-int8 draft params are the bytes term).  The draft
    phase rides its own roofline and adds to the step; a drafter earns
    its keep when the acceptance gain beats its step tax."""
    e_emit = expected_tokens_per_step(acceptance, k)
    t_decode = max(slots * flops_per_token / peak_flops,
                   step_bytes / hbm_bytes_per_s)
    t_draft = max(draft_flops_per_step / peak_flops,
                  draft_bytes_per_step / hbm_bytes_per_s)
    t_verify = max(slots * (k + 1) * flops_per_token / peak_flops,
                   step_bytes / hbm_bytes_per_s) + t_draft
    base = slots / t_decode
    spec = slots * e_emit / t_verify
    rec = {
        "k": float(k),
        "acceptance": acceptance,
        "expected_tokens_per_step": round(e_emit, 4),
        "decode_step_s": t_decode,
        "verify_step_s": t_verify,
        "decode_tokens_per_s": round(base, 1),
        "spec_tokens_per_s": round(spec, 1),
        "speedup": round(spec / base, 3),
    }
    if draft_flops_per_step or draft_bytes_per_step:
        rec["draft_step_s"] = t_draft
    return rec
