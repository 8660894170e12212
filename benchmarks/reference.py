"""The comparisons that decide `correct`, and their tolerances.

They are written against a family's plain float32 forward of one sequence
(`benchmarks/families/<family>.py`'s `logits_at(params, ids, rows, cfg)`,
passed in as `forward`): the comparisons and their limits do not depend
on the block, the forward they call does.  A forward reads the program's
parameter tree (the weights are the system's own, made from the seed) and
nothing else of the program.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


# ---------------------------------------------------------------------------
# the comparisons that decide `correct`
# ---------------------------------------------------------------------------

#: Training: |system loss - reference loss| / reference loss on one seeded
#: sequence.  The system computes in bfloat16 (8 bits of mantissa, relative
#: rounding 2^-8 = 0.4% per element); the mean over a thousand positions
#: averages that down.  A wrong mask, rotation, norm or head moves the
#: logits by O(1) and is caught by LOGIT_RMS_RTOL below; this bound catches
#: a loss that is scaled, shifted or reduced wrongly.
LOSS_RTOL = 5e-3

#: Training: rms(system logits - reference logits) / rms(reference logits)
#: on that sequence.  bfloat16 end to end through the depth kept gives
#: 1-2%; computing in 8-bit floats would give > 6% (2^-4 relative), a
#: structural error ~100%.
LOGIT_RMS_RTOL = 0.04


#: Training: |grad norm the Trainer's step returns - norm of `jax.grad` of
#: the reference loss| / the latter, on one seeded batch at the seed's
#: fresh weights (the caller says why there).  Measured there 7e-5 and
#: 9e-5 (my chip runs, PR 23): the backward pass in bfloat16 moves single
#: elements by ~1% and the norm over 1e9 of them by far less.  A gradient
#: sum over the data-parallel ranks that is dropped or not averaged moves
#: the norm by sqrt(2) to 2, a tensor-parallel partial sum left out by
#: tens of %, a backward in 8-bit floats by several %.
GRAD_NORM_RTOL = 0.01

#: Training: AdamW's second moment after that step against before it.
#: v' = b2 v + (1 - b2) g^2 summed over every element gives the squared
#: norm of the gradient the optimizer consumed, sqrt((sum v' - b2 sum v) /
#: (1 - b2)), which must be the reference gradient's norm after clipping.
#: Float32 sums over 1e9 elements and gradients rounded to bfloat16 before
#: the update agree to ~1e-3; a leaf left out of the update, or an update
#: fed another gradient than the one the norm was taken of, shows.  It is
#: a sum: a leaf that carries under 4% of the squared norm can hide.
ADAM_V_RTOL = 0.02


#: Serving: the share of a stream's tokens that must be the reference's own
#: argmax.  Measured 90-95% (my chip run, PR 23); a wrong page, position or
#: mask gives ~0%.
ARGMAX_SHARE = 0.7


def logit_gap_tolerance(max_logit: float) -> float:
    """Serving: a served token's REFERENCE logit must be within this gap
    of the reference's largest logit at that position, given the stream's
    own prefix.  16 bfloat16 ulps at the magnitude of the winning logit.
    The engine's logits are bfloat16 (spacing 2^-6 between 2 and 4, where
    the winners of a random-weight model lie) after 24 layers of bfloat16
    matmuls, and with 92,544 candidates the reference's top two are often
    closer than that noise: the engine's argmax is the reference's argmax
    or a near tie.  Measured on the chip (PR 23): the largest gap over
    394 served tokens was 0.094 = 6 ulps, 92% of tokens were the
    reference's argmax; 16 ulps leaves room for the tail of some tens of
    thousands of tokens a check of every cell compares.  A page, position
    or mask error moves logits by O(1) = hundreds of ulps; int8 pages add
    several ulps of noise to every logit and fail the argmax share.
    Tokens are not compared one to one: with random weights the largest
    logit changes on rounding.  (`chip_smoke.py` uses 4 ulps against a
    bfloat16 reference over 10 layers.)"""
    return 16.0 * 2.0 ** (math.floor(math.log2(max(abs(max_logit), 1e-6))) - 7)


def check_stream(forward, params, cfg, prompt, tokens, pad_to: int) -> dict:
    """Hold one served greedy stream to the reference `forward`: it runs over
    prompt + tokens[:-1] (right-padded to `pad_to`; causal, so the pad is
    inert) and compare at each generated position."""
    plen, n = len(prompt), len(tokens)
    stream = np.zeros(pad_to, np.int32)
    stream[:plen] = prompt
    stream[plen: plen + n - 1] = tokens[:-1]
    rows = np.arange(plen - 1, plen - 1 + n)
    lg = np.asarray(_logits_jit(forward, cfg)(
        params, jnp.asarray(stream), jnp.asarray(_pad_rows(rows))))
    lg = lg[:n]
    top = lg.max(axis=-1)
    gaps = top - lg[np.arange(n), np.asarray(tokens)]
    tols = np.asarray([logit_gap_tolerance(m) for m in top])
    worst = int(np.argmax(gaps - tols))
    equal = int((lg.argmax(-1) == np.asarray(tokens)).sum())
    return {"ok": bool(np.isfinite(lg).all() and (gaps <= tols).all()
                       and equal >= ARGMAX_SHARE * n),
            "tokens": n, "max_gap": float(gaps.max()),
            "worst_gap": float(gaps[worst]), "tol_there": float(tols[worst]),
            "argmax_equal": equal}


def _pad_rows(rows):
    """Row indices padded to a fixed count so one program serves every
    stream (the pad repeats the last row)."""
    out = np.full(ROW_PAD, rows[-1], np.int32)
    if len(rows) > ROW_PAD:
        raise ValueError(f"{len(rows)} generated tokens > {ROW_PAD}")
    out[: len(rows)] = rows
    return out


#: generated positions compared per stream (the longest output of any
#: traffic file must fit)
ROW_PAD = 384

_JITS = {}


def _logits_jit(forward, cfg):
    key = (forward,) + tuple(sorted((k, v) for k, v in cfg.items()
                                    if isinstance(v, (int, float))))
    if key not in _JITS:
        _JITS[key] = jax.jit(lambda p, ids, rows: forward(p, ids, rows, cfg))
    return _JITS[key]


def check_training(forward, params, cfg, ids, system_logits) -> dict:
    """Hold the system's forward on one sequence `ids` [s] to the
    reference `forward`: its logits [s, vocab] (any float dtype) and the
    loss they give."""
    ids = jnp.asarray(ids)
    n = ids.shape[0]
    ref = jax.jit(lambda p, i: forward(p, i, jnp.arange(n), cfg))(params, ids)

    def loss_of(lg):
        lg = lg[:-1].astype(F32)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.mean(lse - jnp.take_along_axis(lg, ids[1:, None], -1)[:, 0])
    sysl = jnp.asarray(system_logits).astype(F32)
    ref_loss, sys_loss = float(loss_of(ref)), float(loss_of(sysl))
    rms = float(jnp.sqrt(jnp.mean(jnp.square(sysl - ref)))
                / jnp.sqrt(jnp.mean(jnp.square(ref))))
    loss_rel = abs(sys_loss - ref_loss) / abs(ref_loss)
    return {"ok": bool(math.isfinite(sys_loss) and loss_rel <= LOSS_RTOL
                       and rms <= LOGIT_RMS_RTOL),
            "system_loss": sys_loss, "reference_loss": ref_loss,
            "loss_rel_err": loss_rel, "logit_rms_rel_err": rms,
            "loss_rtol": LOSS_RTOL, "logit_rms_rtol": LOGIT_RMS_RTOL}


def _loss_and_grad_norm(forward, params, ids, cfg):
    rows = jnp.arange(ids.shape[1] - 1)

    def seq_loss(p, seq):
        lg = forward(p, seq, rows, cfg)
        lse = jax.nn.logsumexp(lg, axis=-1)
        return jnp.mean(lse - jnp.take_along_axis(lg, seq[1:, None], -1)[:, 0])

    def mean_loss(p):       # vmap: a loop over the sequences (`lax.map`)
        # keeps a second and third copy of the gradients (compiler, PR 23)
        return jnp.mean(jax.vmap(lambda seq: seq_loss(p, seq))(ids))
    loss, g = jax.value_and_grad(mean_loss)(params)
    sq = [jnp.sum(jnp.square(x.astype(F32))) for x in jax.tree.leaves(g)]
    return loss, jnp.sqrt(jnp.sum(jnp.stack(sq)))


def loss_and_grad_norm(forward, params, cfg, ids) -> dict:
    """Mean next-token loss over the sequences `ids` [b, s] (every sequence
    the same weight) and the global norm of its gradient with respect to
    every parameter, by `jax.grad` of the reference `forward`."""
    loss, gnorm = jax.jit(
        lambda p, i: _loss_and_grad_norm(forward, p, i, cfg))(
        params, jnp.asarray(ids))
    return {"loss": float(loss), "grad_norm": float(gnorm)}


def check_train_step(ref: dict, system: dict, clip: float, b2: float) -> dict:
    """Hold one real step of the Trainer on a seeded batch to the
    reference's `loss_and_grad_norm` on the same batch and weights.
    `system`: the `loss` and `grad_norm` the step returned and the sums of
    the optimizer's second moment before and after it (`v_sum_before`,
    `v_sum_after`; None where the optimizer keeps no such state, and then
    that comparison is reported as not made)."""
    loss_rel = abs(system["loss"] - ref["loss"]) / abs(ref["loss"])
    norm_rel = abs(system["grad_norm"] - ref["grad_norm"]) / ref["grad_norm"]
    out = {"step_loss": system["loss"], "reference_step_loss": ref["loss"],
           "step_loss_rel_err": loss_rel,
           "grad_norm": system["grad_norm"],
           "reference_grad_norm": ref["grad_norm"],
           "grad_norm_rel_err": norm_rel, "grad_norm_rtol": GRAD_NORM_RTOL,
           "adam_v_rel_err": None, "adam_v_rtol": ADAM_V_RTOL}
    ok = (math.isfinite(system["loss"]) and loss_rel <= LOSS_RTOL
          and math.isfinite(system["grad_norm"])
          and norm_rel <= GRAD_NORM_RTOL)
    if system.get("v_sum_before") is not None:
        consumed_sq = ((system["v_sum_after"] - b2 * system["v_sum_before"])
                       / (1.0 - b2))
        expected = ref["grad_norm"] * min(1.0, clip / ref["grad_norm"])
        consumed = math.sqrt(max(consumed_sq, 0.0))
        out["adam_v_rel_err"] = abs(consumed - expected) / expected
        ok = ok and out["adam_v_rel_err"] <= ADAM_V_RTOL
    out["ok"] = bool(ok)
    return out
