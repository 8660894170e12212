"""Canonical programs the linter judges: ONE train step, ONE serving
decode, ONE MoE forward+backward, and ONE expert-parallel (ep=2) MoE
step, built the same way every time.

The flag-identity sweep (flag_identity.py) lowers these under each
contracted flag value that their build reads and diffs fingerprints
against an unset environment; tools_lint.py --hlo compiles the train
step once and runs the HLO lints over its post-optimization text.  Both
front ends share these builders so "the canonical program" means exactly
one thing.

Shapes are tiny on purpose (the sweep lowers each program once a flag
it reads): a 2-layer scanned llama on the dp=4 virtual CPU mesh — the
same configuration the per-flag byte-identity tests used before the
sweep replaced them — the 8-slot serving decode program at page 8 /
max_len 32, and a one-block unrolled MoE train step — once on a single
device and once on an ep=2 mesh — so the sweep's identity claims also
cover the routing/dispatch code paths (incl. the HETU_TPU_MOE_DISPATCH
branch point, which only an ep>1 trace reaches).

Every flag under contract acts at Trainer/ServingEngine construction or
at trace time, so the builders construct FRESH objects per call: the
caller scopes the environment (``scoped_env``), then constructs, then
lowers.  A train step's TRACED text is lowered for abstract arguments
(`Trainer.lower_abstract`: the module `build` + `lowered_step` give,
with no parameter initialised — two thirds of a lower's seconds were the
compiles of `init`); a flag value that takes the step off its default
form is refused there by name, which fails the sweep as a moved
fingerprint would.
"""
from __future__ import annotations

import contextlib
import os
from typing import Dict, Iterator, Optional

import numpy as np


@contextlib.contextmanager
def scoped_env(**vals: Optional[str]) -> Iterator[None]:
    """Set (value) or unset (None) env vars for the duration."""
    saved = {k: os.environ.get(k) for k in vals}
    try:
        for k, v in vals.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def canonical_batch(n: int = 8, seq: int = 64,
                    seed: int = 0, vocab: int = 250
                    ) -> Dict[str, np.ndarray]:
    rng = np.random.default_rng(seed)
    ids = rng.integers(1, vocab, size=(n, seq)).astype(np.int32)
    return {"input_ids": ids, "labels": ids.copy()}


def _dense_trainer(dp: int, zero: bool):
    """The canonical train-step owner, not yet built: tiny scanned
    llama, homogeneous dp=4."""
    from hetu_tpu.core.mesh import MeshConfig
    from hetu_tpu.engine import Trainer, TrainingConfig
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
    from hetu_tpu.parallel import ParallelStrategy
    cfg = LlamaConfig.tiny(remat=False, use_scan=True)
    st = ParallelStrategy(mesh=MeshConfig(dp=dp), zero=zero)
    tc = TrainingConfig(global_batch_size=8, micro_batch_size=8 // dp,
                        seq_len=64, lr=1e-3, warmup_steps=2,
                        total_steps=10, log_every=1000)
    return Trainer(LlamaLMHeadModel(cfg, st), tc, st)


def canonical_trainer(dp: int = 4, zero: bool = False):
    """The canonical trainer, built — reads every training-side flag at
    construction or build()."""
    return _dense_trainer(dp, zero).build()


def canonical_compute_dtype() -> Optional[str]:
    """The canonical model's declared compute dtype as the dtype-drift
    lint's token ("bf16"/"f16", None for full-precision) — what
    tools_lint --hlo defaults --expected-dtype to, through the same
    `dtype_token` mapping the HETU_TPU_LINT trainer hook applies to
    model.config."""
    from hetu_tpu.analysis.hlo_lints import dtype_token
    from hetu_tpu.models.llama import LlamaConfig
    return dtype_token(
        LlamaConfig.tiny(remat=False, use_scan=True).compute_dtype)


def _step_text(tr, batch: Dict[str, np.ndarray], optimized: bool) -> str:
    """A trainer's step for its configured batch, under the CURRENT
    environment: the traced module (the sweep's fingerprint surface), or
    with optimized=True the post-optimization HLO of the built trainer's
    compile (the HLO lints' input)."""
    try:
        if not optimized:
            return tr.lower_abstract().as_text()
        return tr.build().lowered_step(batch, optimized=True)
    finally:
        tr.close()


def train_step_text(*, optimized: bool = False, dp: int = 4,
                    zero: bool = False) -> str:
    """Lowered text of the canonical train step."""
    return _step_text(_dense_trainer(dp, zero), canonical_batch(), optimized)


def _moe_trainer(mesh):
    """One UNROLLED MoE llama block (sort dispatch, 4 experts, top-2),
    not yet built — tiny because the sweep lowers it once a flag it
    reads, unrolled because the numerics observatory's router taps live
    at the loss-trace level (scanned layer bodies cannot hand values
    out; documented in docs/observability.md)."""
    from hetu_tpu.engine import Trainer, TrainingConfig
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
    from hetu_tpu.parallel import ParallelStrategy
    cfg = LlamaConfig.tiny(
        remat=False, use_scan=False, num_experts=4, moe_top_k=2,
        num_hidden_layers=1, hidden_size=32, intermediate_size=64,
        vocab_size=128, num_attention_heads=2, num_key_value_heads=2,
        max_position_embeddings=64, moe_capacity_factor=1.0)
    st = ParallelStrategy(mesh=mesh)
    tc = TrainingConfig(global_batch_size=4, micro_batch_size=4,
                        seq_len=16, lr=1e-3, warmup_steps=2,
                        total_steps=10, log_every=1000)
    return Trainer(LlamaLMHeadModel(cfg, st), tc, st)


def canonical_moe_batch(seed: int = 0) -> Dict[str, np.ndarray]:
    return canonical_batch(n=4, seq=16, seed=seed, vocab=120)


def moe_step_text(*, optimized: bool = False) -> str:
    """Lowered text of the canonical MoE forward+backward step on a
    single device — the sweep's third program, covering the MoE code
    path (routing, sort dispatch, expert einsums, aux losses) that
    neither the dense train step nor the serving decode exercises."""
    from hetu_tpu.core.mesh import MeshConfig
    return _step_text(_moe_trainer(MeshConfig(dp=1)),
                      canonical_moe_batch(), optimized)


def moe_ep_step_text(*, optimized: bool = False) -> str:
    """Lowered text of the same MoE step on an ep=2 mesh — the sweep's
    fourth program, whose trace actually reaches the ep>1 branch point
    in `nn/moe.py` (HETU_TPU_MOE_DISPATCH reads there), so the dispatch
    flag's gspmd identity contract covers the code path it gates and a
    regression that perturbs the ep lowering under any contracted flag
    fails the sweep."""
    from hetu_tpu.core.mesh import MeshConfig
    return _step_text(_moe_trainer(MeshConfig(ep=2)),
                      canonical_moe_batch(), optimized)


def serving_decode_text(*, optimized: bool = False) -> str:
    """Lowered text of the canonical serving decode program under the
    CURRENT environment (flags read through ServeConfig.from_flags and
    the engine's build-time kernel routing).  optimized=True pays one
    XLA compile and returns the post-optimization HLO (the lints'
    input); the default traced text is the sweep's fingerprint
    surface."""
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
    from hetu_tpu.serving import ServeConfig, ServingEngine
    cfg = LlamaConfig(vocab_size=256, hidden_size=64,
                      num_hidden_layers=2, num_attention_heads=4,
                      num_key_value_heads=2, max_position_embeddings=256,
                      use_flash_attention=False, remat=False,
                      use_scan=True)
    model = LlamaLMHeadModel(cfg)
    import jax
    params = model.init(jax.random.key(0))
    eng = ServingEngine(model, params, ServeConfig.from_flags(
        page_size=8, max_len=32, prefill_chunk=8))
    try:
        args = list(eng._dummy_args("decode"))
        args[0] = params
        lowered = eng._jits["decode"].lower(*args)
        return (lowered.compile().as_text() if optimized
                else lowered.as_text())
    finally:
        eng.close()


#: program name -> builder of its (unoptimized) lowered text — the
#: sweep's program axis
PROGRAMS = {
    "train": train_step_text,
    "decode": serving_decode_text,
    "moe": moe_step_text,
    "moe_ep": moe_ep_step_text,
}
