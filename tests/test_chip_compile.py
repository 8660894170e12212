"""Compile the main path for a DESCRIBED TPU v5e — no chip attached.

The TPU compiler is installed in the test sandbox and compiles for a chip
that is described, not attached (`get_topology_desc`).  That shows what
interpret mode cannot: a block shape the Mosaic lowering refuses, more
VMEM than a kernel may use, a kernel the partitioner cannot split.  Each
of these compiled under interpret mode for twenty PRs and was refused
the first time the whole program met the chip's compiler (PR 21: the
fused-norm backward's (1, hidden) partial block, the rotary kernel's
row block at seq 40/300/1100, every kernel under a multi-device mesh —
repaired by a padded partial block, an overhanging row block and a
per-shard shard_map respectively).

One case per main-path kernel at Llama-2-7B widths, then the three whole
programs `chip_smoke.py` runs on the chip.  Nothing executes: these
tests say a program COMPILES for v5e, never how it runs.

The code under test asks `jax.default_backend()` (interpret mode,
kernel routing) and would take its CPU branch here, so the module
fixture steers that one call — in the test, not through an option of
the program.
"""
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else it logs under /tmp
# libtpu lets one process at a time load it (/tmp/libtpu_lockfile).  Nothing
# here touches a chip, and under pytest-xdist every worker imports this
# module: without this, all but one worker would skip it and the workers
# would disagree on what was collected.
os.environ.setdefault("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")

import jax
import jax.numpy as jnp
import pytest
from jax.experimental import topologies
from jax.sharding import SingleDeviceSharding

try:
    TOPO = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
except Exception as e:  # no TPU compiler in this installation
    pytest.skip(f"cannot describe a TPU v5e here: {e!r}",
                allow_module_level=True)

ONE_CHIP = SingleDeviceSharding(TOPO.devices[0])
BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32

# Llama-2-7B widths (models/llama/config.py llama2_7b)
HIDDEN, INTER, HEADS, HEAD_DIM, VOCAB, SEQ = 4096, 11008, 32, 128, 32000, 4096


@pytest.fixture(scope="module", autouse=True)
def described_chip():
    """Route and lower as on a TPU backend, with the persistent compile
    cache off: an entry written for a described chip cannot be read back
    without one, and the next compile would warn about it."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    patch = pytest.MonkeyPatch()
    patch.setattr(jax, "default_backend", lambda: "tpu")
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was_on)
    cc.reset_cache()
    patch.undo()


def spec(shape, dtype, sharding=ONE_CHIP):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def compile_text(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def scalar_loss(fn):
    """fwd+bwd of `fn`: grads of the f32 sum of squares of its outputs
    w.r.t. every argument (squares, so that the backward of a LINEAR
    kernel still depends on the arguments — jit drops unused arguments,
    and with them the described device the program is compiled for)."""
    def loss(*args):
        return sum(jnp.square(o.astype(F32)).sum()
                   for o in jax.tree.leaves(fn(*args)))
    return lambda *args: jax.grad(loss, argnums=tuple(range(len(args))))(*args)


# ---------------------------------------------------------------------------
# kernels, one case each
# ---------------------------------------------------------------------------

def _flash(n_kv):
    from hetu_tpu.ops.pallas.flash_attention import flash_attention
    q = spec((1, SEQ, HEADS, HEAD_DIM), BF16)
    kv = spec((1, SEQ, n_kv, HEAD_DIM), BF16)
    return scalar_loss(lambda q, k, v: flash_attention(q, k, v)), (q, kv, kv)


def _norm(bwd):
    from hetu_tpu.ops.pallas.fused_norm import fused_residual_rmsnorm
    x = spec((2, SEQ, HIDDEN), BF16)
    fn = fused_residual_rmsnorm
    return (scalar_loss(fn) if bwd else fn), (x, x, spec((HIDDEN,), BF16))


def _swiglu():
    from hetu_tpu.ops.pallas.swiglu import fused_swiglu
    g = spec((2, SEQ, INTER), BF16)
    return scalar_loss(fused_swiglu), (g, g)


def _rotary(batch, seq, bwd=True):
    """seq 4096 is training; 40, 300 and 1100 are prompts whose row block
    has to be a multiple of 8 (the PR 21 refusal) — 300 and 1100 have no
    such divisor in budget, so their last block overhangs; 1 is the decode
    step."""
    from hetu_tpu.ops.pallas.rotary import fused_rotary_qk
    q = spec((batch, seq, HEADS, HEAD_DIM), BF16)
    t = spec((batch, seq, HEAD_DIM // 2), F32)

    def fn(q, k):   # tables are inputs, not differentiated
        return fused_rotary_qk(q, k, jnp.ones(t.shape, F32),
                               jnp.zeros(t.shape, F32))
    return (scalar_loss(fn) if bwd else fn), (q, q)


def _paged(quant, n_kv, verify_c=0):
    """8 slots x 2048 positions of 16-token pages, as chip_smoke serves."""
    from hetu_tpu.ops.pallas.paged_attention import (paged_attention,
                                                     paged_verify)
    slots, page, pages, max_pages = 8, 16, 8 * 128 + 1, 128
    q_shape = ((slots, verify_c, HEADS, HEAD_DIM) if verify_c
               else (slots, HEADS, HEAD_DIM))
    pool = spec((pages, page, n_kv, HEAD_DIM), jnp.int8 if quant else BF16)
    scales = ((spec((pages, page, n_kv), F32),) * 2 if quant else ())
    kernel = paged_verify if verify_c else paged_attention

    def fn(q, k, v, table, pos, *sc):
        kw = dict(k_scale=sc[0], v_scale=sc[1]) if sc else {}
        return kernel(q, k, v, table, pos, **kw)
    return fn, (spec(q_shape, BF16), pool, pool,
                spec((slots, max_pages), I32), spec((slots,), I32), *scales)


def _paged_serving_cell():
    """The paged kernel as the benchmark's InternLM2 serving cells call
    it inside the layer scan: 32 slots, 16 query / 8 KV heads of 128,
    128 table entries of 16-token pages, bfloat16, and as the pool every
    layer's 2048 pages in ONE flat array, the layer's pages reached
    through the table (`models/generation._paged_forward`)."""
    from hetu_tpu.ops.pallas.paged_attention import paged_attention
    slots, layers, pages = 32, 24, 2048
    pool = spec((layers * pages, 16, 8, HEAD_DIM), BF16)

    def fn(q, k, v, table, pos):
        return paged_attention(q, k, v, table + 5 * pages, pos)
    return fn, (spec((slots, 16, HEAD_DIM), BF16), pool, pool,
                spec((slots, 128), I32), spec((slots,), I32))


def _chunk_attn(C, nq, n_kv, M, window=None, hd=HEAD_DIM, sink=False):
    """The chunk program's attention as a serving cell's layer calls it
    (`KVAttention.attend_dense`): one row's chunk against the layer's
    slab of the scratch, or the window + C positions sliced out of it;
    `hd`: keys wider than the values; `sink`: one a query head."""
    from hetu_tpu.ops.pallas.chunk_attention import chunk_attention

    def fn(q, k, v, start, first, *sinks):
        return chunk_attention(q, k, v, start, window=window, first=first,
                               **({"sink": sinks[0]} if sinks else {}))
    return fn, (spec((1, C, nq, hd), BF16), spec((1, M, n_kv, hd), BF16),
                spec((1, M, n_kv, HEAD_DIM), BF16), spec((1,), I32),
                spec((), I32), *((spec((nq,), F32),) if sink else ()))


def _latent_chunk_attn(C, nh, M):
    """The chunk program's attention over a latent cache as a serving
    cell's MLA layer calls it (`kimi_k2.MLAttention.attend_dense`): one
    row's chunk against the scratch's latents of 576 values in 640
    lanes, a head's k_nope | v made inside the kernel by W_kvb."""
    from hetu_tpu.ops.pallas.latent_chunk_attention import \
        latent_chunk_attention
    return (lambda *a: latent_chunk_attention(*a, softmax_scale=192 ** -0.5),
            (spec((1, C, nh, 128), BF16), spec((1, C, nh, 64), BF16),
             spec((1, M, 640), BF16), spec((512, nh, 256), BF16),
             spec((1,), I32)))


def _kda_scan():
    """The chunkwise delta rule at the Ling cell's shape: one chunk of
    2,048 rows, 32 heads' 128 x 128 states, the operands as the
    projections leave them, q and k made unit length inside."""
    from hetu_tpu.ops.pallas.kda_scan import kda_scan
    cols = spec((1, 2048, 32 * 128), F32)
    return (lambda *a: kda_scan(*a, g_floor=-5.0, qk_scale=128 ** -0.5),
            (spec((1, 32, 128, 128), F32), cols, cols, cols, cols,
             spec((1, 2048, 32), F32), spec((1,), I32)))


def _selective_scan():
    """The Mamba-1 scan at the Jamba and Phi cells' shape: one chunk of
    512 rows, 5,120 channels of 16 state lanes, u', B and C in bfloat16
    as the projections leave them."""
    from hetu_tpu.ops.pallas.selective_scan import selective_scan
    cols = lambda dt: spec((1, 512, 5120), dt)  # noqa: E731
    return selective_scan, (
        spec((1, 16, 5120), F32), cols(BF16), cols(F32),
        spec((16, 5120), F32), spec((1, 512, 16), BF16),
        spec((1, 512, 16), BF16), spec((5120,), F32), spec((1,), I32))


def _quant(bits):
    from hetu_tpu.ops.pallas.quant import quantize_blockwise_pallas
    return (lambda x: quantize_blockwise_pallas(x, 128, bits=bits),
            (spec((HIDDEN * INTER,), F32),))


KERNEL_CASES = {
    "flash_fwd_bwd_mha": lambda: _flash(HEADS),
    "flash_fwd_bwd_gqa": lambda: _flash(8),
    "norm_fwd": lambda: _norm(bwd=False),
    "norm_fwd_bwd": lambda: _norm(bwd=True),
    "swiglu_fwd_bwd": _swiglu,
    "rotary_fwd_bwd_train": lambda: _rotary(2, SEQ),
    "rotary_prompt_40": lambda: _rotary(2, 40, bwd=False),
    "rotary_prompt_300": lambda: _rotary(2, 300, bwd=False),
    "rotary_prompt_1100": lambda: _rotary(1, 1100, bwd=False),
    "rotary_decode_step": lambda: _rotary(8, 1, bwd=False),
    "paged_attention_fp": lambda: _paged(False, HEADS),
    "paged_attention_fp_gqa": lambda: _paged(False, 8),
    "paged_attention_int8": lambda: _paged(True, HEADS),
    "paged_verify_c5": lambda: _paged(False, HEADS, verify_c=5),
    "paged_attention_serving_cell": _paged_serving_cell,
    "chunk_attention_trinity_full": lambda: _chunk_attn(512, 32, 4, 8192),
    "chunk_attention_trinity_window": lambda: _chunk_attn(
        512, 32, 4, 2560, window=2048),
    "chunk_attention_internlm2": lambda: _chunk_attn(128, 16, 8, 2048),
    # MiMo's window layers: the band form (tiles of 8 heads x 128 positions)
    "chunk_attention_mimo_window_band": lambda: _chunk_attn(
        1024, 64, 8, 1152, window=128, hd=256, sink=True),
    "latent_chunk_attention_ling": lambda: _latent_chunk_attn(
        2048, 32, 32768),
    "latent_chunk_attention_kimi": lambda: _latent_chunk_attn(512, 64, 4096),
    "kda_scan_ling_chunk": _kda_scan,
    "selective_scan_jamba_chunk": _selective_scan,
    "quant_int8": lambda: _quant(8),
    "quant_int4": lambda: _quant(4),
}


@pytest.mark.parametrize("case", KERNEL_CASES)
def test_kernel_compiles_for_v5e(case):
    fn, args = KERNEL_CASES[case]()
    assert "tpu_custom_call" in compile_text(fn, *args)


def test_paged_kernel_takes_the_serving_cells_pool_as_it_lies():
    """At the serving cells' shape the kernel reads the flat pool of all
    layers where it lies: the pools are whole-array HBM operands
    (`pl.ANY`), so the program around the call holds no temporary, let
    alone a copy of a 1.6 GB pool in another layout (PR 27 found a 2.0 GB
    layout copy of the latent pool this way), and the kernel's double
    buffers fit the default VMEM of a call with room to spare."""
    from hetu_tpu.ops.pallas import paged_attention as pa
    fn, args = _paged_serving_cell()
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    ppb = pa.pages_per_block(16, 16, 8, HEAD_DIM, 2, 128)
    assert ppb * 16 == pa._BLOCK_TOKENS
    assert ppb * 16 * pa._token_vmem_bytes(16, 8, HEAD_DIM, 2, "none") \
        <= pa._VMEM_BUDGET


#: the four cells' decode launches of the paged LATENT kernel: (slots,
#: query heads, table width, pages a cache layer + the null page, cache
#: layers in the one flat pool); pages of 256 tokens of 640 lanes
LATENT_CELLS = {
    "xing4": (16, 32, 132, 2129, 5),
    "longcat": (96, 64, 10, 977, 8),
    "kimi": (64, 64, 16, 1041, 6),
    "ling": (32, 32, 128, 4113, 1),
}


@pytest.mark.parametrize("cell", LATENT_CELLS)
def test_latent_kernel_takes_the_serving_cells_pool_as_it_lies(cell):
    """At each latent cell's shape the kernel walks the flat pool of all
    cache layers where it lies (`MLAttention.attend_paged`: the layer's
    pages reached through `table + base`): a whole-array HBM operand
    (`pl.ANY`), so the program around the call holds no temporary, let
    alone PR 27's 2.0 GB layout copy of a latent pool; the block the
    rule chose is 1,024 tokens (4 pages), and its double buffers and
    float32 scores fit the budget and the call's default VMEM (the
    compile is the witness)."""
    from hetu_tpu.ops.pallas import paged_latent_attention as pla
    slots, heads, width, pages, layers = LATENT_CELLS[cell]
    pool = spec((layers * pages, 256, 640), BF16)

    def fn(q, pool, table, pos):
        return pla.paged_latent_attention(
            q, pool, table + (layers - 1) * pages, pos, value_dim=512,
            softmax_scale=0.1)
    compiled = jax.jit(fn).lower(
        spec((slots, heads, 640), BF16), pool, spec((slots, width), I32),
        spec((slots,), I32)).compile()
    calls = [ln for ln in compiled.as_text().splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1 and "pallas_paged_latent_attention" in calls[0]
    assert compiled.memory_analysis().temp_size_in_bytes < 1 << 20
    ppb = pla.pages_per_block(heads, 256, 640, 2, width)
    assert ppb * 256 == pla._BLOCK_TOKENS == 1024
    assert ppb * 256 * pla._token_vmem_bytes(heads, 640, 2) \
        <= pla._VMEM_BUDGET


# ---------------------------------------------------------------------------
# the AdamW update as `auto` runs it: XLA's chain, a leaf where it lies
# ---------------------------------------------------------------------------

#: the leaves of both train cells as the update sees them: Mistral-7B's
#: (2 layers stacked) whole, InternLM2-1.8B's (16 layers) as ONE SHARD of
#: dp2 x tp2 + ZeRO holds them
ADAM_CELL_LEAVES = {
    "mistral_lm_head": (4096, 32768),
    "mistral_embed": (32768, 4096),
    "mistral_o_proj": (2, 4096, 4096),
    "mistral_wqkv": (2, 4096, 8, 6, 128),
    "mistral_down_proj": (2, 14336, 4096),
    "mistral_w_gate_up": (2, 4096, 2, 14336),
    "mistral_norm": (2, 4096),
    "mistral_final_norm": (4096,),
    "internlm2_shard_w_gate_up": (8, 2048, 2, 4096),
    "internlm2_shard_down_proj": (8, 4096, 2048),
    "internlm2_shard_wqkv": (8, 2048, 4, 4, 128),
    "internlm2_shard_o_proj": (8, 1024, 2048),
    "internlm2_shard_embed": (46272, 1024),
    "internlm2_shard_lm_head": (1024, 46272),
    "internlm2_shard_norm": (8, 2048),
    "internlm2_shard_final_norm": (1024,),
}


@pytest.mark.parametrize("leaf", ADAM_CELL_LEAVES)
def test_adamw_update_reads_a_cell_leaf_where_it_lies(leaf):
    """`AdamW.update` over each leaf of the two train cells, compiled for
    the described v5e with nothing forced: XLA's chain (no kernel), and
    nothing that moves the leaf — no `reshape`, `copy` or `transpose`
    instruction, no temporary to speak of.  (Behind the kernel a leaf's
    p, g, m and v were each copied to `[n/128, 128]` and the three
    results copied back: 3.29 GB of temporaries for three leaves, three
    quarters of the optimizer's time, PR 39.)"""
    from hetu_tpu.optim.optimizer import AdamW
    opt = AdamW(lr=3e-4, weight_decay=0.1)
    tree = lambda dt: {"w": spec(ADAM_CELL_LEAVES[leaf], dt)}
    state = {"step": spec((), I32), "m": tree(F32), "v": tree(F32)}
    compiled = jax.jit(
        lambda params, grads, state: opt.update(grads, state, params),
        donate_argnums=(0, 2)).lower(tree(BF16), tree(F32), state).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    # (of an array: the step counter, a scalar, is copied to scalar memory)
    assert not re.findall(
        r"= \w+\[\d[\d,]*\]\S* (?:reshape|copy|transpose)\(.*", text)
    assert compiled.memory_analysis().temp_size_in_bytes < 64 << 20


# ---------------------------------------------------------------------------
# whole programs, as chip_smoke.py runs them
# ---------------------------------------------------------------------------

def _trainer(strategy, batch=2):
    """The Trainer of `chip_smoke.py`'s train phases over described
    devices: the 2-layer Llama-2-7B-width model at batch 2 x seq 4096."""
    from hetu_tpu.core.mesh import create_mesh
    from hetu_tpu.engine.trainer import Trainer
    from hetu_tpu.engine.trainer_config import TrainingConfig
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel

    cfg = LlamaConfig.llama2_7b(num_hidden_layers=2, param_dtype=BF16,
                                remat_policy="dots_attn")
    tc = TrainingConfig(global_batch_size=batch,
                        micro_batch_size=batch // max(strategy.dp, 1),
                        seq_len=SEQ)
    return Trainer(LlamaLMHeadModel(cfg, strategy), tc, strategy,
                   mesh=create_mesh(strategy.mesh, devices=TOPO.devices))


def _train_step(strategy, batch=2):
    """(the Trainer, its step compiled for described devices), lowered by
    `Trainer.lower_abstract` — no parameter is materialised."""
    trainer = _trainer(strategy, batch)
    return trainer, trainer.lower_abstract().compile()


def _assert_every_train_kernel(trainer, compiled):
    """flash, norm, swiglu and rotary: each routed to Pallas by the shape
    gate (nothing forced), each a tpu_custom_call in the program.  The
    AdamW update is XLA's (PR 39; the kernel went in PR 60): no route is
    asked for it and no instruction of the program carries the
    `pallas_adam` scope."""
    from chip_smoke import TRAIN_KERNELS, kernels_in
    routes = dict(trainer.kernel_routes)
    assert sorted(routes) == sorted(TRAIN_KERNELS), routes
    for name, rec in routes.items():
        assert rec["pallas"] and not rec["xla"], (name, rec)
        assert list(rec["why"]) == ["shape gate passes"], (name, rec)
    text = compiled.as_text()
    found = kernels_in(text)
    assert all(found[k] for k in TRAIN_KERNELS), found
    assert "pallas_adam" not in text


def test_train_step_compiles_for_one_v5e_with_every_kernel():
    """The donated AdamW step `chip_smoke.py`'s train phase runs, with
    flash, norm, swiglu and rotary routed to Pallas and the update left
    to XLA, inside one chip's 16 GB."""
    from hetu_tpu.parallel import ParallelStrategy
    trainer, compiled = _train_step(ParallelStrategy())
    _assert_every_train_kernel(trainer, compiled)
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


@pytest.fixture(scope="module")
def sharded_step():
    """(trainer, compiled): dp2 x tp2 + sequence parallel + ZeRO over the
    four described chips — `chip_smoke.py --chips 4`."""
    from hetu_tpu.core.mesh import MeshConfig
    from hetu_tpu.parallel import ParallelStrategy
    return _train_step(ParallelStrategy(mesh=MeshConfig(dp=2, tp=2),
                                        sequence_parallel=True, zero=True))


def test_sharded_train_step_compiles_for_four_v5e_with_every_kernel(
        sharded_step):
    """GSPMD cannot partition a Mosaic call (the lowering raises), so each
    kernel runs once per shard inside a shard_map over the layouts the
    model declares (ops/pallas.per_shard): the same four kernels as on one
    chip, and the collectives of the partitioned program around them."""
    trainer, compiled = sharded_step
    _assert_every_train_kernel(trainer, compiled)
    text = compiled.as_text()
    assert "all-gather" in text and "all-reduce" in text
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


def test_sharded_zero_step_reduce_scatters_a_layers_gradients_in_the_scan(
        sharded_step):
    """Under ZeRO a layer's weight gradients are reduce-scattered over dp
    into the state's shards where the backward scan makes them (PR 59).
    With the state split BETWEEN layers (the rule before) the body held
    one tuple all-reduce over the dp pairs of a layer's four matrices,
    twice the bytes, sliced after the loop; split INSIDE the layer
    (`optim.zero_shardings`), the state's sharding propagates back into
    the loop: no all-reduce over the dp pairs in the backward body, and
    four `all-reduce-scatter` fusions there whose output is half their
    operand — wqkv, o_proj, gate|up, down_proj."""
    from hetu_tpu.core.mesh import mesh_axis_group
    from hetu_tpu.obs import hlo_text as H
    from hetu_tpu.obs.comm import grad_sync_report
    trainer, compiled = sharded_step
    text = compiled.as_text()
    dp_pair = mesh_axis_group(trainer.mesh, "dp")
    assert dp_pair == (0, 2)
    comps = H.split_computations(text)
    trips = H.while_multipliers(comps)
    layers = trainer.model.config.num_hidden_layers
    backward = [name for name, lines in comps.items()
                if trips[name] == (layers, False)
                and any("transpose(jvp" in ln for ln in lines)]
    assert len(backward) == 1, backward
    whole, scattered = [], []
    for line in comps[backward[0]]:
        if " fusion(" in line and "all-reduce-scatter" in line:
            inner = next(ln for ln in comps[H.CALLEE_PAT.search(line).group(1)]
                         if H.maybe_collective(ln))
            if H.first_group(inner, 4)[1] == dp_pair:
                scattered.append(
                    (H.shape_bytes(H.OUT_PAT.search(line).group(1)),
                     H.shape_bytes(H.OUT_PAT.search(inner).group(1))))
        found = H.maybe_collective(line)
        if found and found[0] == "all-reduce" \
                and H.first_group(line, 4)[1] == dp_pair:
            whole.append(line.strip()[:160])
    assert not whole, whole
    assert len(scattered) == 4, scattered
    assert all(2 * out == operand for out, operand in scattered), scattered
    # what the four carry: a tp shard (half) of a layer's matrices, bf16
    c = trainer.model.config
    shard_bytes = 2 * (c.hidden_size * (c.num_attention_heads
                                        + 2 * c.num_key_value_heads) * HEAD_DIM
                       + c.hidden_size * c.hidden_size
                       + 3 * c.hidden_size * c.intermediate_size) // 2
    assert sum(operand for _, operand in scattered) == shard_bytes
    # and the trainer's count of the same text: a scatter sends half its
    # operand; beside the layers' and the head's, only the norm gains'
    # vectors are left, all-reduced over dp x tp
    sync = grad_sync_report(text, dp_pair, default_world=4)
    assert sync["reduce_scatter"] == 4 * layers + 1, sync
    head_bytes = 2 * c.hidden_size * VOCAB // 2
    assert 1.0 <= sync["wire_bytes"] / (
        (layers * shard_bytes + head_bytes) / 2) < 1.001, sync
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


@pytest.mark.parametrize("zero", [False, True])
def test_one_chip_train_step_is_the_same_program_under_the_zero_rule(
        zero, monkeypatch):
    """Without a dp axis `zero_shardings` returns the parameters'
    shardings before it reads any spec: the one-chip step lowers to the
    same text under the rule of PR 59 and under the one before it
    (shapes that know of no stack), ZeRO asked for or not — so it
    compiles to the same program (`mistral7b-train-1chip`)."""
    import hetu_tpu.optim.optimizer as O
    from hetu_tpu.parallel import ParallelStrategy

    def lowered_text():
        return _trainer(ParallelStrategy(zero=zero)).lower_abstract(
            ).as_text()

    with_rule = lowered_text()
    rule = O.zero_shardings
    monkeypatch.setattr(
        O, "zero_shardings",
        lambda pshard, specs, mesh, axis="dp": rule(
            pshard, jax.tree.map(
                lambda s: s.abstract(), specs,
                is_leaf=lambda s: hasattr(s, "abstract")), mesh, axis))
    assert lowered_text() == with_rule


def _serving_engine(cfg=None, num_slots=8, model=None, **serve):
    """The engine `chip_smoke.py` serves with — 8 slots x 2048 positions
    of 16-token pages, 2 layers at Llama-2-7B widths, or `model` — over
    abstract parameters (programs are built lazily, nothing is
    materialised but the zeroed pool)."""
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
    from hetu_tpu.serving.engine import ServeConfig, ServingEngine

    if model is None:
        model = LlamaLMHeadModel(cfg or LlamaConfig.llama2_7b(
            num_hidden_layers=2, param_dtype=BF16))
    sc = ServeConfig(**{**dict(num_slots=num_slots, page_size=16,
                               max_len=2048, prefill_chunk=128), **serve})
    params = jax.tree.map(lambda a: spec(a.shape, a.dtype),
                          model.abstract_params())
    return ServingEngine(model, params, sc)


def _took_paged_kernel(engine) -> bool:
    """Every K/V layer the engine's LOWERED programs traced took the
    paged kernel (the route is a layer's own: `attend_paged`)."""
    took = engine.kernel_routes["paged_attn"]
    return bool(took["pallas"]) and not took["xla"]


def _gpt_block():
    """Two GPT blocks (LayerNorm, learned positions, biased fused QKV,
    GELU) at 16 heads of 128: the paged kernel's lane gate refuses
    GPT-2's own head_dim of 64, whose layers take the composition over
    gathered pages."""
    from hetu_tpu.models.gpt import GPTConfig, GPTLMHeadModel
    return GPTLMHeadModel(GPTConfig(
        vocab_size=50304, hidden_size=2048, num_hidden_layers=2,
        num_attention_heads=16, max_position_embeddings=2048,
        param_dtype=BF16)), {}


def _kimi_block():
    """Kimi-K2's dense layer and ONE expert layer at published widths,
    12 of 384 experts held, at the serving cell's page and chunk."""
    from hetu_tpu.models.kimi_k2 import KimiK2Config, KimiK2LMHeadModel
    return KimiK2LMHeadModel(KimiK2Config(
        vocab_size=20480, num_hidden_layers=2, experts_held=12,
        rope_scaling={"type": "yarn", "factor": 64, "beta_fast": 32,
                      "beta_slow": 1, "mscale": 1, "mscale_all_dim": 1,
                      "original_max_position_embeddings": 4096},
        param_dtype=BF16)), dict(page_size=256, max_len=4096,
                                 prefill_chunk=512)


def _trinity_block():
    """Trinity-Mini at published widths, 16 of 128 experts held, two
    dense layers and one whole period of expert layers (three that read
    a window of 2,048 and one that reads everything), at the serving
    cell's slots, page, chunk and max_len, pages by kind of layer."""
    from hetu_tpu.models.trinity import TrinityConfig, TrinityLMHeadModel
    return TrinityLMHeadModel(TrinityConfig(
        vocab_size=25024, num_hidden_layers=8, experts_held=16,
        param_dtype=BF16)), dict(num_slots=32, page_size=16, max_len=8192,
                                 prefill_chunk=512, num_pages=(4000, 3700))


def _mimo_block():
    """MiMo-V2-Flash at published widths, 16 of 256 experts held: the
    dense layer that reads everything (4 KV heads), a window layer and a
    full layer over experts (window 128 with a sink, 8 KV heads; keys of
    192 stored in 256 lanes beside values of 128), at the serving cell's
    slots, page, chunk and max_len: pages and scratch by kind of layer,
    each kind its own shapes."""
    from hetu_tpu.models.mimo_v2 import MiMoV2Config, MiMoV2LMHeadModel
    return MiMoV2LMHeadModel(MiMoV2Config(
        vocab_size=19072, num_hidden_layers=3, experts_held=16,
        hybrid_layer_pattern=(0, 1, 0), moe_layer_freq=(0, 1, 1),
        param_dtype=BF16)), dict(num_slots=16, page_size=64, max_len=16384,
                                 prefill_chunk=1024, num_pages=(4096, 48))


def _ling_block():
    """Ling-3.0-flash at published widths, one routing group of 64 of
    512 experts held: the dense KDA layer, a KDA layer and the MLA layer
    over experts (layer_group_size 3 puts the MLA layer third), at the
    serving cell's slots, page, chunk and max_len: the state of 32 slots
    beside the latent pages of one layer."""
    from hetu_tpu.models.bailing_hybrid import (BailingHybridConfig,
                                                BailingHybridLMHeadModel)
    return BailingHybridLMHeadModel(BailingHybridConfig(
        vocab_size=19648, num_hidden_layers=3, first_k_dense_replace=1,
        layer_group_size=3, experts_held=64, param_dtype=BF16)), dict(
            num_slots=32, page_size=256, max_len=32768, prefill_chunk=1024,
            num_pages=4112)


def _phi4flash_block():
    """Phi-4-mini-flash at published widths, 8 of 32 layers (one (Mamba,
    window) pair, the memory layer, the full layer, two (GMU, cross)
    pairs) and a cut vocabulary, at the serving cell's slots, page,
    chunk and max_len: Mamba state by slot beside window and full pages,
    K and V stored as 2 rows of 640 lanes."""
    from hetu_tpu.models.phi4_flash import (Phi4FlashConfig,
                                            Phi4FlashLMHeadModel)
    return Phi4FlashLMHeadModel(Phi4FlashConfig(
        vocab_size=20480, num_hidden_layers=8, param_dtype=BF16,
        compute_dtype=BF16)), dict(
            num_slots=32, page_size=128, max_len=24576, prefill_chunk=512,
            num_pages=(6160, 176), max_prefilling=4)


def _jamba_whole():
    """AI21-Jamba2-3B WHOLE at published widths (28 layers: runs of 7,
    13 and 6 Mamba-1 layers scanned, layers 7 and 21 attention over ONE
    K/V head), at the serving cell's slots, page, chunk and max_len: 26
    layers of state by slot beside two layers of one-head pages."""
    from hetu_tpu.models.jamba import JambaConfig, JambaLMHeadModel
    return JambaLMHeadModel(JambaConfig(
        param_dtype=BF16, compute_dtype=BF16)), dict(
            num_slots=128, page_size=128, max_len=4608, prefill_chunk=512,
            num_pages=4624, max_prefilling=8)


def _longcat_cut():
    """LongCat-Flash-Chat as its cell serves it: the WHOLE cut (4
    published layers = 8 latent cache layers, 16 of 512 routed experts
    held, all 256 identity experts, 16,384 vocabulary rows) at published
    widths, at the cell's slots, page, chunk and max_len."""
    from benchmarks import traffic
    from benchmarks.families import longcat_flash
    cfg = traffic.load_json("configs", "longcat-flash-ep32-depth4")
    sv = cfg["serving"]
    return longcat_flash.build_model(cfg, sv), {k: sv[k] for k in (
        "num_slots", "page_size", "max_len", "prefill_chunk", "num_pages")}


def _scan_is_the_kernel(routes, chunk_text, decode_calls):
    """A Mamba family's chunk program runs `ops/pallas/selective_scan.py`
    once a traced Mamba layer body, under `ssm_scan`; its decode program
    keeps `selective_scan.step`, the composition."""
    rec = routes["selective_scan"]
    scans = [ln for ln in chunk_text
             if 'custom_call_target="tpu_custom_call"' in ln
             and "pallas_selective_scan" in ln]
    assert rec["pallas"] == len(scans) > 0 and not rec["xla"], rec
    assert list(rec["why"]) == ["shape gate passes"]
    assert all("ssm_scan/pallas_selective_scan" in ln for ln in scans)
    assert not any("pallas_selective_scan" in ln for ln in decode_calls)


def _xing4_cut():
    """Xing4.0-29B-A4B as its cell serves it: the WHOLE cut (1 dense + 4
    expert layers, all 64 experts and all 131,072 vocabulary rows) at
    published widths, at the cell's slots, page, chunk and max_len."""
    from benchmarks import traffic
    from benchmarks.families import xing4
    cfg = traffic.load_json("configs", "xing4.0-29b-a4b-depth5")
    sv = cfg["serving"]
    return xing4.build_model(cfg, sv), {k: sv[k] for k in (
        "num_slots", "page_size", "max_len", "prefill_chunk", "num_pages")}


def _deepseek_v32_cut():
    """DeepSeek-V3.2 as its cell serves it: the WHOLE cut (1 dense + 4
    expert layers, 8 of 256 experts a layer, 16,160 vocabulary rows) at
    published widths (128 heads, the 64 x 128 indexer, index_topk 2,048),
    at the cell's slots, page, chunk and max_len."""
    from benchmarks import traffic
    from benchmarks.families import deepseek_v32
    cfg = traffic.load_json("configs", "deepseek-v3.2-ep32-depth5")
    sv = cfg["serving"]
    return deepseek_v32.build_model(cfg, sv), {k: sv[k] for k in (
        "num_slots", "page_size", "max_len", "prefill_chunk", "num_pages")}


def _lfm2_cut():
    """LFM2-8B-A1B as its cell serves it: layers 0-11 as published (9
    gated short convolutions, 3 attention layers of 64-wide heads stored
    two a lane row, both dense layers, 10 expert layers with all 32
    experts) and the whole vocabulary, at the cell's slots, page, chunk
    and max_len."""
    from benchmarks import traffic
    from benchmarks.families import lfm2_moe
    cfg = traffic.load_json("configs", "lfm2-8b-a1b-depth12")
    sv = cfg["serving"]
    return lfm2_moe.build_model(cfg, sv), {k: sv[k] for k in (
        "num_slots", "page_size", "max_len", "prefill_chunk", "num_pages",
        "max_prefilling")}


#: family -> ((model, ServeConfig overrides) or None for the Llama-2-7B
#: block, the kernels its decode program must hold as tpu_custom_calls)
SERVING_FAMILIES = {
    "llama": (None, ("paged_attn", "rotary", "swiglu")),
    "gpt": (_gpt_block, ("paged_attn",)),
    "kimi": (_kimi_block, ("paged_latent",)),
    "trinity": (_trinity_block, ("paged_attn",)),
    "mimo": (_mimo_block, ("paged_attn",)),
    "ling": (_ling_block, ("paged_latent",)),
    "phi4flash": (_phi4flash_block, ("paged_attn",)),
    "jamba": (_jamba_whole, ("paged_attn",)),
    "longcat": (_longcat_cut, ("paged_latent",)),
    "xing4": (_xing4_cut, ("paged_latent",)),
    "deepseek_v32": (_deepseek_v32_cut, ()),
    "lfm2": (_lfm2_cut, ("paged_attn",)),
}
#: the families whose case runs from a file of its own
#: (tests/test_chip_compile_longcat.py, .._xing4.py, .._deepseek_v32.py,
#: .._lfm2.py): a file is what one worker of the tier-1 run takes whole,
#: and this one is among the longest
ELSEWHERE = ("longcat", "xing4", "deepseek_v32", "lfm2")


@pytest.fixture(scope="module")
def decode_text():
    """family -> its decode program's text as compiled for one v5e, once
    for the module: the case that compiles a family's programs hands its
    lowering in (a `Lowered` keeps its executable: no second compile),
    a case that runs alone lowers its own."""
    kept = {}

    def of(family, lowered=None):
        if family not in kept:
            if lowered is None:
                model, serve = SERVING_FAMILIES[family][0]()
                lowered = _serving_engine(model=model, **serve) \
                    .lower_programs(sharding=ONE_CHIP)["decode"]
            kept[family] = lowered.compile().as_text()
        return kept[family]
    return of


@pytest.mark.parametrize("family", [
    f for f in SERVING_FAMILIES if f not in ELSEWHERE])
def test_serving_programs_compile_for_one_v5e(family, decode_text):
    """The engine's decode step (with the family's paged-attention kernel
    walking the page tables), prefill chunk and page write, for 8 slots x
    2048 positions (Kimi: 4096, in its cell's 256-token pages).  One set
    of programs (models/generation.py) for every family: the GPT block
    had never met the chip's compiler before it shared every line with
    the ones that run in three cells."""
    from chip_smoke import KERNEL_SCOPES

    make, kernels = SERVING_FAMILIES[family]
    model, serve = make() if make else (None, {})
    engine = _serving_engine(model=model, **serve)
    programs = engine.lower_programs(sharding=ONE_CHIP)
    # the chunk program at every launch shape the engine can issue: one
    # to four chunks of the Llama and GPT blocks' 128 rows, one chunk of
    # the families' own 512 to 2,048 (`engine.launch_multiples`)
    larger = [f"prefill_chunk_x{k}" for k in (2, 3, 4)
              if k * engine.config.prefill_chunk <= 512]
    assert bool(larger) == (family in ("llama", "gpt"))
    assert sorted(programs) == sorted(
        ["decode", "prefill_chunk", "write_pages", *larger])
    assert engine.kernel_routes["prefill_launch_rows"]["rows"] == [
        k * engine.config.prefill_chunk for k in range(1, len(larger) + 2)]
    compiled = {name: low.compile() for name, low in programs.items()}
    calls = [ln for ln in decode_text(
        family, programs["decode"]).splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    routes = engine.kernel_routes
    for k in kernels:
        scope = KERNEL_SCOPES.get(k, f"pallas_{k}_attention")
        assert any(scope in ln for ln in calls), (k, len(calls))
        assert routes[k]["pallas"] and not routes[k]["xla"], routes
    if "paged_latent" in kernels:
        # the reason says the block the wrapper's rule chose: 1,024 tokens
        assert list(routes["paged_latent"]["why"]) == [
            "shape gate passes, pages_per_block=4"]
    # the chunk program of a K/V family asks the blockwise kernel's gate
    # in every layer it traces: Trinity's 168 / 537 MB of float32 scores
    # a layer take it (two calls a layer: K and V relaid head-major, then
    # the attention), 33.5 MB at 32 heads x 128 x 2,048 do not pay for it
    # and keep the composition; a latent cache has a kernel and a route
    # of its own (`MLAttention.attend_dense`): one call an MLA layer,
    # under `attn`, nothing relaid around it
    chunk_text = compiled["prefill_chunk"].as_text().splitlines()
    chunk_calls = sum("pallas_chunk_attention" in ln for ln in chunk_text
                      if 'custom_call_target="tpu_custom_call"' in ln)
    latent_calls = [ln for ln in chunk_text
                    if 'custom_call_target="tpu_custom_call"' in ln
                    and "pallas_latent_chunk_attention" in ln]
    if family in ("kimi", "ling", "longcat", "xing4"):
        rec = routes["latent_chunk_attn"]
        assert rec["pallas"] == len(latent_calls) == {
            "kimi": 2, "ling": 1, "longcat": 8, "xing4": 5}[family] \
            and not rec["xla"], rec
        assert list(rec["why"]) == ["shape gate passes"]
        assert all("attn/pallas_latent_chunk_attention" in ln
                   for ln in latent_calls)
        assert not any("pallas_latent_chunk_attention" in ln for ln in calls)
    elif family == "deepseek_v32":
        # the blockwise latent kernel once a layer, under the scope of the
        # attention over the selection and GIVEN the selection's mask (an
        # int8 operand [rows, positions] beside the latents); the paged
        # latent kernel is not on this family's path: the decode step
        # gathers the selected entries
        rec = routes["latent_chunk_attn"]
        assert rec["pallas"] == len(latent_calls) == 5 and not rec["xla"]
        assert all("attn/dsa_attend/pallas_latent_chunk_attention" in ln
                   and f"s8[{engine.config.prefill_chunk},33792]" in ln
                   for ln in latent_calls)
        assert "paged_latent" not in routes
        assert not any("paged_latent" in ln for ln in calls)
    else:
        assert "latent_chunk_attn" not in routes and not latent_calls
    if family == "deepseek_v32":
        # TWO page arrays under one table (the latents and the indexer's
        # keys), carried in place; a decode pass gathers the index keys of
        # every slot's table (132 pages) and then AT MOST 2,048 latents a
        # slot a layer, whatever the context;
        # the five scopes stand in both programs; weights + pool + the
        # largest program's temporaries fit the chip
        S = engine.config.num_slots
        assert [a.shape for a in engine.pool.arrays.tree()] == [
            (5, S * 133 + 1, 256, 640), (5, S * 133 + 1, 256, 128)]
        pool = sum(a.size * a.dtype.itemsize
                   for a in engine.pool.arrays.tree())
        assert S == 16 and pool == 5 * 2129 * 256 * (640 + 128) * 2
        mem = {name: c.memory_analysis() for name, c in compiled.items()}
        assert mem["decode"].alias_size_in_bytes >= pool
        assert mem["decode"].temp_size_in_bytes < 0.3e9
        assert mem["prefill_chunk"].temp_size_in_bytes < 1.0e9
        assert 6.4e9 < 2 * engine.model.num_params() < 6.5e9
        assert all(m.argument_size_in_bytes + m.temp_size_in_bytes
                   < 15.2e9 for m in mem.values())
        text = decode_text(family, programs["decode"])
        latents = [ln for ln in text.splitlines()
                   if " gather(" in ln and ",640]" in ln.split(" gather(")[0]]
        assert len(latents) == 5 and all(
            f"bf16[{S},2048,640]" in ln for ln in latents)
        keys = {ln.split(" = ")[1].split("{")[0] for ln in text.splitlines()
                if " gather(" in ln and ",256,128]" in ln.split(" gather(")[0]}
        assert keys == {f"bf16[{S},132,256,128]"}
        for name in ("decode", "prefill_chunk"):
            body = compiled[name].as_text()
            assert all(f"/{scope}/" in body for scope in (
                "dsa_index_q", "dsa_index_k", "dsa_score", "dsa_select",
                "dsa_attend")), name
    if family == "ling":
        # state beside pages: both programs take the state arrays as
        # donated arguments and hand them back in place, with the pool
        # (the decode program) or the scratch (the chunk program): no
        # copy of either among the temporaries
        assert "chunk_attn" not in routes and not chunk_calls
        nbytes = lambda tree: sum(  # noqa: E731
            a.size * a.dtype.itemsize for a in tree)
        state, pool = nbytes(engine.pool.state), nbytes(
            engine.pool.arrays.tree())
        assert state == 33 * 2 * (2_097_152 + 73_728)
        mem = {name: c.memory_analysis() for name, c in compiled.items()}
        assert mem["decode"].alias_size_in_bytes >= pool + state
        assert mem["decode"].temp_size_in_bytes < 64e6
        assert mem["prefill_chunk"].alias_size_in_bytes >= state + 32768 * 1280
        assert mem["prefill_chunk"].temp_size_in_bytes < 0.3e9
        text = compiled["decode"].as_text()
        assert "kda_step" in text
        # the chunk program walks each KDA layer's blocks in one kernel
        # under the `kda_scan` scope; the decode program takes none
        rec = routes["kda_scan"]
        assert rec["pallas"] == 2 and not rec["xla"], rec
        assert list(rec["why"]) == ["shape gate passes"]
        scans = [ln for ln in compiled["prefill_chunk"].as_text().splitlines()
                 if 'custom_call_target="tpu_custom_call"' in ln
                 and "pallas_kda_scan" in ln]
        assert len(scans) == 2 and all("kda_scan/pallas_kda_scan" in ln
                                       for ln in scans)
        assert not any("pallas_kda_scan" in ln for ln in calls)
    elif family == "phi4flash":
        # a decode pass: the paged kernel once a distinct attention layer
        # of the program (window, full, cross), over stored rows of 640
        # lanes: 10 rows of 128 are refused by its page copies (Mosaic:
        # "must be aligned to tiling (8)": the first compile of PR 43).
        # The chunk program: the blockwise kernel in the window layers
        # (every row), the composition for the ONE row that reads the
        # full layer's cache in the tail; both carry the Mamba state in
        # place and hold one body a layer of a period, not one a layer
        assert len(calls) == 3 and sum(
            "pallas_paged_attention_window" in ln for ln in calls) == 1
        assert routes["paged_attn"]["pallas"] and not routes[
            "paged_attn"]["xla"]
        rec = routes["chunk_attn"]
        assert 2 * rec["pallas"] == chunk_calls == 2 and rec["xla"]
        assert engine.pool.arrays.k.shape == (1, 6161, 128, 2, 640)
        nbytes = lambda tree: sum(  # noqa: E731
            a.size * a.dtype.itemsize for a in tree)
        state, pool = nbytes(engine.pool.state), nbytes(
            engine.pool.arrays.tree())
        assert state == 33 * 3 * (327_680 + 30_720)
        mem = {name: c.memory_analysis() for name, c in compiled.items()}
        assert mem["decode"].alias_size_in_bytes >= pool + state
        assert mem["decode"].temp_size_in_bytes < 64e6
        assert mem["prefill_chunk"].temp_size_in_bytes < 0.3e9
        text = compiled["prefill_chunk"].as_text()
        assert "ssm_scan" in text and "tail" in text
        assert "ssm_step" in compiled["decode"].as_text()
        # the scan is the kernel in every Mamba layer body of the chunk
        # program (a period's scanned layers and the tail's: a decision a
        # traced body), never in the decode program
        _scan_is_the_kernel(routes, chunk_text, calls)
    elif family == "jamba":
        # ONE K/V head of bfloat16: the paged kernel takes both attention
        # layers (it refused such pages until PR 47: a token's row is
        # under a 32-bit word) over pages of [128, 128] at the model's
        # own bytes, and the chunk kernel the group of 20 query heads as
        # one tall operand, nothing relaid (one call a layer); the pool
        # and the 26 layers of state are carried in place
        assert len(calls) == 2 and all(
            "attn_full/pallas_paged_attention" in ln for ln in calls)
        rec = routes["chunk_attn"]
        assert rec["pallas"] == chunk_calls == 2 and not rec["xla"], rec
        assert engine.pool.arrays.k.shape == (2, 4625, 128, 1, 128)
        nbytes = lambda tree: sum(  # noqa: E731
            a.size * a.dtype.itemsize for a in tree)
        state, pool = nbytes(engine.pool.state), nbytes(
            engine.pool.arrays.tree())
        assert state == 129 * 26 * (327_680 + 30_720)
        assert pool == 2 * 2 * 4625 * 128 * 128 * 2
        mem = {name: c.memory_analysis() for name, c in compiled.items()}
        assert mem["decode"].alias_size_in_bytes >= pool + state
        assert mem["decode"].temp_size_in_bytes < 0.2e9
        assert mem["prefill_chunk"].alias_size_in_bytes >= state
        assert mem["prefill_chunk"].temp_size_in_bytes < 0.5e9
        assert "ssm_norm" in compiled["prefill_chunk"].as_text()
        assert "ssm_norm" in compiled["decode"].as_text()
        _scan_is_the_kernel(routes, chunk_text, calls)
    elif family == "lfm2":
        # heads of 64 stored two a lane row: to both kernels grouped-query
        # attention of 32 heads over 4 rows of 128 (the paged kernel once
        # an attention layer; the chunk kernel and its relayout, two calls
        # a layer), never the composition; the pool holds the model's own
        # 2,048 B a token a layer and, with the 9 layers' tails [9, 129,
        # 4096], is carried in place; the page write moves a prompt's
        # pages and no more (a scatter of whole pages of 4 rows a token
        # copied the pool, 1.8 GB of temporaries: serving/kv_pool.py);
        # the operator's scopes stand in both programs; weights + pool +
        # the largest program's temporaries fit the chip
        paged = [ln for ln in calls if "pallas_paged_attention" in ln]
        assert len(paged) == 3 == routes["paged_attn"]["pallas"] and all(
            "attn_full/pallas_paged_attention" in ln for ln in paged)
        rec = routes["chunk_attn"]
        assert 2 * rec["pallas"] == chunk_calls == 6 and not rec["xla"], rec
        assert list(rec["why"]) == ["shape gate passes"]
        assert engine.pool.arrays.k.shape == (3, 4625, 128, 4, 128)
        nbytes = lambda tree: sum(  # noqa: E731
            a.size * a.dtype.itemsize for a in tree)
        state, pool = nbytes(engine.pool.state), nbytes(
            engine.pool.arrays.tree())
        assert state == 129 * 9 * 8192
        assert pool == 2 * 3 * 4625 * 128 * 2048 // 2
        mem = {name: c.memory_analysis() for name, c in compiled.items()}
        assert mem["decode"].alias_size_in_bytes >= pool + state
        assert mem["decode"].temp_size_in_bytes < 0.1e9
        assert mem["prefill_chunk"].alias_size_in_bytes >= state
        # (a chunk of 1,536 rows: 6,144 pairs' gate|up rows among them)
        assert mem["prefill_chunk"].temp_size_in_bytes < 0.5e9
        assert mem["write_pages"].temp_size_in_bytes < 1 << 20
        assert 2 * engine.model.num_params() == 7_857_456_512
        assert all(m.argument_size_in_bytes + m.temp_size_in_bytes
                   < 12.0e9 for m in mem.values())
        for name in ("decode", "prefill_chunk"):
            text = compiled[name].as_text()
            assert all(f"/{scope}/" in text for scope in (
                "short_conv", "short_conv_proj", "short_conv_mix",
                "router", "experts")), name
    elif family in ("kimi", "deepseek_v32"):
        assert "chunk_attn" not in routes and not chunk_calls
    elif family == "longcat":
        # TWO latent cache layers a published layer: the paged latent
        # kernel 8 times a decode pass, the latent chunk kernel 8 times
        # a chunk, one expert layer a published layer (4 routers); the
        # pool (976 pages x 8 cache layers of 640 lanes) is carried in
        # place, and weights + pool + the largest program's temporaries
        # fit the chip
        assert "chunk_attn" not in routes and not chunk_calls
        assert sum("pallas_paged_latent_attention" in ln
                   for ln in calls) == 8 == routes["paged_latent"]["pallas"]
        assert engine.pool.arrays.k.shape == (8, 977, 256, 640)
        pool = sum(a.size * a.dtype.itemsize
                   for a in engine.pool.arrays.tree())
        assert pool == 8 * 977 * 256 * 640 * 2
        mem = {name: c.memory_analysis() for name, c in compiled.items()}
        assert mem["decode"].alias_size_in_bytes >= pool
        assert mem["decode"].temp_size_in_bytes < 0.3e9
        assert mem["prefill_chunk"].temp_size_in_bytes < 1.0e9
        weights = 2 * engine.model.num_params() + 4 * 2 * 6144 * 768
        assert all(m.argument_size_in_bytes + m.temp_size_in_bytes
                   < 15.5e9 for m in mem.values())
        assert mem["decode"].argument_size_in_bytes > weights + pool
        for name in ("decode", "prefill_chunk"):
            text = compiled[name].as_text()
            assert text.count("zero_experts") and "router" in text
    elif family == "xing4":
        # Kimi's sublayers inside the stream: both latent kernels once a
        # layer, at 132 pages a slot and 33,792 positions of scratch; the
        # pool (2,128 pages x 5 layers of 640 lanes) is carried in place,
        # the stream is the carry ([1, 1024, 4, 3584]: the compiler lays
        # it stream-major, whole tiles of positions x lanes, nothing
        # padded), its three scopes stand in both programs, and weights
        # + pool + the largest program's temporaries fit the chip
        assert "chunk_attn" not in routes and not chunk_calls
        assert sum("pallas_paged_latent_attention" in ln
                   for ln in calls) == 5 == routes["paged_latent"]["pallas"]
        assert engine.pool.arrays.k.shape == (5, 2129, 256, 640)
        pool = sum(a.size * a.dtype.itemsize
                   for a in engine.pool.arrays.tree())
        assert pool == 5 * 2129 * 256 * 640 * 2
        mem = {name: c.memory_analysis() for name, c in compiled.items()}
        assert mem["decode"].alias_size_in_bytes >= pool
        assert mem["decode"].temp_size_in_bytes < 0.1e9
        assert mem["prefill_chunk"].temp_size_in_bytes < 0.3e9
        assert 8.0e9 < 2 * engine.model.num_params() < 8.2e9
        assert all(m.argument_size_in_bytes + m.temp_size_in_bytes
                   < 15.2e9 for m in mem.values())
        assert mem["decode"].argument_size_in_bytes \
            > 2 * engine.model.num_params() + pool
        for name in ("decode", "prefill_chunk"):
            text = compiled[name].as_text()
            assert all(f"layer/{scope}/" in text for scope in (
                "mhc_pre", "mhc_sinkhorn", "mhc_post"))
            assert "attn/mhc_" not in text and "mlp/mhc_" not in text
        assert "bf16[1,1024,4,3584]{3,1,2,0:T(8,128)(2,1)" in \
            compiled["prefill_chunk"].as_text()
    elif family == "trinity":
        rec = routes["chunk_attn"]
        assert 2 * rec["pallas"] == chunk_calls == 16 and not rec["xla"]
        assert list(rec["why"]) == ["shape gate passes"]
    elif family == "mimo":
        # both kinds of layer take both kernels at their own shapes: keys
        # of 256 lanes against values of 128, groups of 16 and 8, the
        # window layer with its sink; the window layer's scratch holds
        # 128 + 1,024 positions, the full layers' 16,384.  The window
        # layer's chunk attention takes the band form (a tile is its 8
        # heads at ONE block of 128 positions and walks 3 key blocks of
        # 128, not the slice's 1,152 keys); the two full layers say why not
        rec = routes["chunk_attn"]
        assert 2 * rec["pallas"] == chunk_calls == 6 and not rec["xla"]
        band = routes["chunk_attn_band"]
        assert (band["pallas"], band["xla"]) == (1, 2), band
        assert sorted(w.split(":")[0] for w in band["why"]) == [
            "128 positions a tile see 255 keys", "no window"]
        assert any(w.endswith("pb 128, kb 128, steps 3") for w in band["why"])
        assert routes["paged_attn_window"]["pallas"] == 1
        why = routes["paged_attn_shapes"]["why"]
        assert sorted(why.values()) == [1, 2] and any(
            "groups of 8, a sink a head" in w for w in why)
        assert engine._scratch_positions == (16384, 1152)
        # (154 MB at the cell's 1,024 rows, in either tiling)
        assert compiled["prefill_chunk"].memory_analysis() \
            .temp_size_in_bytes < 0.2e9
    else:
        # one gate for every launch shape: the float32 scores of a layer,
        # heads x rows x 2,048 positions, from 64 MB on take the kernel
        # (two calls a traced layer) and under it keep the composition.
        # Llama's 32 heads: 33.5 MB at one chunk, 67-134 MB at two to
        # four; the GPT block's 16: the four-chunk launch alone (67 MB)
        rec = routes["chunk_attn"]
        heads = engine.model.config.num_attention_heads
        pays = {name: 4 * heads * rows * 2048 >= 64 << 20 for name, rows in
                zip(["prefill_chunk", *larger],
                    routes["prefill_launch_rows"]["rows"])}
        assert not pays["prefill_chunk"] and pays["prefill_chunk_x4"]
        assert rec["pallas"] == sum(pays.values())
        assert rec["xla"] == len(pays) - rec["pallas"], rec
        assert len(rec["why"]) == 1 + rec["xla"] and all(
            w == "shape gate passes" or "MB of float32 scores" in w
            for w in rec["why"]), rec
        for name, kernel in pays.items():
            text = compiled[name].as_text().splitlines()
            assert sum("pallas_chunk_attention" in ln for ln in text
                       if 'custom_call_target="tpu_custom_call"' in ln) \
                == 2 * kernel, name
    if family == "trinity":
        # both kinds of layer decode through the kernel, each under its
        # own name; no program copies a pool (a scatter of whole pages
        # at 4 KV heads a row did: serving/kv_pool._write_pages_kinds)
        # or holds the float32 scores of all heads at once
        assert routes["paged_attn_window"]["pallas"] == 6
        assert sum("pallas_paged_attention_window" in ln for ln in calls) \
            == 6 and len(calls) >= 8
        pool = sum(a.size * a.dtype.itemsize
                   for a in engine.pool.arrays.tree())
        temps = {name: c.memory_analysis().temp_size_in_bytes
                 for name, c in compiled.items()}
        assert temps["write_pages"] < 1e6 and temps["decode"] < pool / 20
        # (0.19 GB with the composition's float32 scores, one KV head
        # at a time: PR 34; 0.10 GB with the blockwise kernel)
        assert temps["prefill_chunk"] < 0.15e9, temps


@pytest.mark.parametrize("family", ["phi4flash", "jamba"])
def test_decode_program_leaves_no_product_without_a_scope(
        family, decode_text):
    """The decode programs of the two Mamba families as the v5e compiler
    writes them (`.clone` fusions, `bitcast_fusion`s and copy pairs
    without metadata): `obs.scope_map` places every top-level fusion
    that holds a product, and of the top-level instructions that run
    something (not parameters, constants, tuples and their elements) NO
    rule places under 8% of all (loop counters and conditions, the
    bodies of reductions: PR 53 read 5.7% for Phi, 3.7% for Jamba)."""
    from hetu_tpu.obs import hlo_profile as hp
    from hetu_tpu.obs.hlo_text import DEF_PAT, split_computations

    text = decode_text(family)
    groups, sources = hp.scope_map(text), hp.scope_sources(text)
    comps = split_computations(text)
    fused = {c for lines in comps.values() for ln in lines
             if " fusion(" in ln
             for c in re.findall(r"calls=%?([\w.\-]+)", ln)}
    top = [DEF_PAT.search(ln) for c, lines in comps.items()
           if c not in fused for ln in lines]
    top = [(m.group(1), m.group(3)) for m in top if m]
    products = [name for name, op in top if op == "fusion" and any(
        " convolution(" in ln for c in re.findall(
            r"calls=%?([\w.\-]+)", next(
                ln for lines in comps.values() for ln in lines
                if f"%{name} = " in ln)) for ln in comps[c])]
    assert len(products) > 10
    assert not [n for n in products if groups[n][0] == hp.UNSCOPED]
    assert len([n for n, op in top if sources[n] == "none"
                and op not in hp._ALIASES]) < 0.08 * len(top)


@pytest.fixture(scope="module")
def internlm2_cell():
    """kv_quant -> (the engine of the benchmark's serving cells,
    InternLM2-1.8B at 32 slots and 2048 pages; name -> that program
    compiled for one v5e), each built and compiled once for the module:
    two tests read the exact pool's decode program."""
    from hetu_tpu.models.llama import LlamaConfig
    cfg = LlamaConfig(vocab_size=92544, hidden_size=2048,
                      intermediate_size=8192, num_hidden_layers=24,
                      num_attention_heads=16, num_key_value_heads=8,
                      max_position_embeddings=32768, rope_theta=1e6,
                      param_dtype=BF16)
    kept = {}

    def of(kv_quant="none"):
        if kv_quant not in kept:
            engine = _serving_engine(cfg, num_slots=32, num_pages=2048,
                                     kv_quant=kv_quant)
            programs = engine.lower_programs(sharding=ONE_CHIP)
            compiled = {}
            kept[kv_quant] = engine, lambda name: compiled.setdefault(
                name, programs[name].compile())
        return kept[kv_quant]
    return of


@pytest.mark.parametrize("kv_quant", ["none", "int8"])
def test_decode_program_updates_the_pool_in_place(kv_quant, internlm2_cell):
    """The donated KV pool is ONE buffer from the decode program's
    argument to its result (PR 25).  As an xs -> ys of the layer scan it
    was sliced, stacked and copied whole three times a step (24 ms of a
    56 ms decode step, and a second pool in memory: PERF.md s6); this is
    the guard that keeps those copies from coming back:

      * the program's temporaries are under a quarter of the pool — for
        the model and the pool of the benchmark's serving cells
        (InternLM2-1.8B, 32 slots, 2048 pages: 3.2 GB of bf16 pages).
        At Llama-2-7B widths a layer's weights sliced out of the stack
        (ROADMAP queue 1, 2b) are 450 MB of temporaries by themselves,
        and which layout the compiler keeps the scale planes in changes
        with the shape, so the guard is on the shape that is served;
      * no copy, dynamic-slice or dynamic-update-slice — by opcode or by
        the name the compiler gives the fusion around one — yields a
        whole pool array (as stored, or as the flat view the scan
        carries) or one layer's slab of page payload.  One layer's SCALE
        plane (int8 pages) is still sliced and converted for the kernel,
        which reads scales in a lane-padded layout: 1 MB a layer, and
        what `_paged_forward` says it does."""
    import re
    engine, program = internlm2_cell(kv_quant)
    pool = list(engine.pool.arrays.tree())
    compiled = program("decode")
    assert _took_paged_kernel(engine)
    pool_bytes = sum(a.size * a.dtype.itemsize for a in pool)
    assert compiled.memory_analysis().temp_size_in_bytes < pool_bytes / 4

    def dims(shape):
        return ",".join(map(str, shape))
    banned = set()
    for a in pool:
        L, P = a.shape[:2]
        banned |= {dims(a.shape), dims((L * P,) + a.shape[2:])}
        if a.ndim == 5:     # page payload: a layer's slab too
            banned |= {dims(a.shape[1:]), dims((1,) + a.shape[1:])}
    moves = re.compile(r"copy|dynamic[-_]slice|dynamic[-_]update[-_]slice")
    inst = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \(?\w+\[([\d,]*)\]"
                      r"[^ ]* ([\w\-]+)\(")
    found, scatters = [], 0
    for line in compiled.as_text().splitlines():
        m = inst.match(line)
        if not m:
            continue
        name, shape, opcode = m.groups()
        if shape in banned and moves.search(name + " " + opcode):
            found.append(f"{name} [{shape}] {opcode}")
        scatters += opcode == "scatter" and shape in banned
    assert not found, found
    # ... and the token IS written there: one in-place scatter per array
    assert scatters == len(pool), scatters


def test_serving_programs_move_no_bytes_a_layer_does_not_read(
        internlm2_cell):
    """The InternLM2 serving cells' decode and chunk programs (PR 32).

    * The weights: a product takes its matrix out of the layers' stack
      inside its own fusion.  In the training layout ([.., 2, I],
      [.., n_kv, g + 2, hd]) the compiler staged a layer's 64 + 16 MB in
      a `constant_dynamic-slice_fusion` of their own at every layer of
      every execution (0.75 + 0.19 s of the chat trace's 4 s: ledger,
      PR 31); over the model's serving view (`serving_params`) no
      instruction outside a fusion (copy, dynamic-slice, or a fusion
      named for one) yields a whole layer's weight.
    * The scratch: the chunk program's dense cache [L, 1, max_len, ...]
      is a carry of the layer walk and donated: aliased from argument to
      result, and no second one among the temporaries."""
    import re
    engine, program = internlm2_cell()
    assert engine.relaid_weight_bytes == 2_013_265_920
    assert engine.kernel_routes["relaid_weight_bytes"] == 2_013_265_920
    assert _took_paged_kernel(engine)
    compiled = {name: program(name) for name in ("decode", "prefill_chunk")}

    # one layer's part of every stacked matrix: [h, w], or [1, h, w]
    layer = set()
    for w in jax.tree.leaves(engine.params["model"]["layers"]):
        if w.ndim >= 3:
            dims = ",".join(map(str, w.shape[1:]))
            layer |= {dims, "1," + dims}
    moves = re.compile(r"copy|dynamic[-_]slice")
    inst = re.compile(r"^\s*(?:ROOT )?%?([\w.\-]+) = \(?\w+\[([\d,]*)\]"
                      r"[^ ]* ([\w\-]+)\(")
    head = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) \(.*\) -> .* \{\s*$")
    for name, c in compiled.items():
        found, fused = [], False
        for line in c.as_text().splitlines():
            h = head.match(line)
            if h:
                fused = "fused_computation" in h.group(1)
                continue
            m = inst.match(line)
            if m and not fused and m.group(2) in layer \
                    and moves.search(m.group(1) + " " + m.group(3)):
                found.append(f"{m.group(1)} [{m.group(2)}] {m.group(3)}")
        assert not found, (name, found)

    scratch = sum(a.size * a.dtype.itemsize
                  for a in engine._fresh_scratch())
    assert scratch == 201_326_592
    mem = compiled["prefill_chunk"].memory_analysis()
    assert mem.alias_size_in_bytes >= scratch
    assert mem.temp_size_in_bytes < scratch / 4

