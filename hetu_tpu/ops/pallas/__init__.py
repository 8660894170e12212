"""Hand-written Pallas TPU kernels for the hot ops
(reference: hetu/impl/kernel/*.cu — the ~10% of kernels XLA fusion does not
already cover; SURVEY.md §2.5 item 2).

The fused-kernel layer (docs/kernels.md):

  * flash_attention  — online-softmax attention (FlashAttention.cu)
  * fused_norm       — residual-add + RMSNorm / LayerNorm, one pass
                       (FusedLayerNorm/RMSNorm.cu)
  * swiglu           — silu(gate) * up combine (SwiGLU.cu)
  * rotary           — RoPE applied to q AND k in one kernel (rotary.cu)
  * quant            — blockwise int8/int4 quantize/dequantize feeding the
                       compressed collectives (quantization.cu, EQuARX)
  * paged_attention  — decode attention directly over the serving KV
                       pool's page tables (gather-free decode)
  * paged_verify     — the multi-query sibling: k+1 speculative query
                       positions per slot attend the same pages in one
                       launch (spec-decode verification)
  * paged_latent_attention — the paged decode kernel of latent (MLA)
                       attention: one pool of [c_kv | k_rope] vectors,
                       a slot's live pages walked in blocks by
                       paged_attention's walk, each block fetched once
                       and used as key and value
  * chunk_attention  — the chunk program's attention over a dense K/V
                       cache (chunked prefill): blockwise, online
                       softmax, only the key blocks a chunk can see
  * latent_chunk_attention — the same over a dense LATENT cache (MLA):
                       a head's k_nope | v made from a key block's
                       latents inside the kernel; shares chunk_
                       attention's online softmax
  * kda_scan         — the chunkwise gated delta rule of a linear-
                       attention layer (ops/delta_rule.chunk_scan): a
                       head's blocks walked inside one launch, its state
                       resident in VMEM
  * selective_scan   — the selective scan of a Mamba-1 layer
                       (ops/selective_scan.chunk_scan): a chunk's
                       positions walked inside one launch, the state
                       resident in VMEM and, 512 lanes at a time, in
                       registers
  * sample           — fused last-layer epilogue: lm_head matmul +
                       temperature/top-k/top-p filter + Gumbel draw per
                       row without materializing [rows, vocab] logits

Every kernel follows the flash-attention pattern: a shape gate that
EXACTLY mirrors the kernel's own entry validation (`compatible()` /
ValueError — the drift tests in tests/test_pallas_kernels.py pin the two
together), an XLA fallback the dispatcher in `hetu_tpu/ops` routes to
when the gate rejects or the flag says off, `interpret=_interpret()` on
the CPU test mesh, and a custom_vjp backward so training paths get the
fused bytes too.

Routing: `HETU_TPU_PALLAS` (auto/1/0) gates the WHOLE layer the way it
always gated flash attention; `HETU_TPU_PALLAS_KERNELS` restricts which
kernels participate (comma list / all / none) so one kernel can be
bisected out without losing the rest.  `resolve_route` is the one rule
every dispatcher asks; under a multi-device mesh the kernel runs once per
shard of the layouts the caller declares (`per_shard`), and every
decision taken while a program is traced is recorded with its reason
(`record_routes` -> `Trainer.kernel_routes`, `ServingEngine.kernel_routes`).
"""
from __future__ import annotations

import contextlib
import threading
from typing import FrozenSet, Optional, Tuple

#: every routable kernel name (the HETU_TPU_PALLAS_KERNELS vocabulary)
KERNEL_NAMES = ("flash", "norm", "swiglu", "rotary", "quant", "paged_attn",
                "paged_verify", "sample", "paged_latent",
                "chunk_attn", "kda_scan", "latent_chunk_attn",
                "selective_scan")


def _interpret() -> bool:
    """CPU (the virtual test mesh) runs kernels in interpret mode — one
    definition shared by every kernel module."""
    import jax
    return jax.default_backend() == "cpu"


def fit_sublane_block(n: int, cap: int) -> int:
    """The largest block of an `n`-long second-minor axis that divides
    `n`, stays within `cap` and that the TPU lowering accepts: a multiple
    of 8 (the sublane tile), or `n` itself.  0 when there is none — the
    shape gates turn that into a ValueError, so the XLA path takes the
    shape instead of a kernel the compiler refuses."""
    if n <= cap:
        return n
    r = cap - cap % 8
    while r and n % r:
        r -= 8
    return r


def _selected_kernels() -> FrozenSet[str]:
    from hetu_tpu.utils import flags
    raw = flags.str_flag("HETU_TPU_PALLAS_KERNELS").strip()
    if raw in ("", "all"):
        return frozenset(KERNEL_NAMES)
    if raw == "none":
        return frozenset()
    names = frozenset(t.strip() for t in raw.split(",") if t.strip())
    unknown = names - frozenset(KERNEL_NAMES)
    if unknown:
        raise ValueError(
            f"HETU_TPU_PALLAS_KERNELS names unknown kernels {sorted(unknown)}; "
            f"known: {list(KERNEL_NAMES)} (or 'all'/'none')")
    return names


def kernel_enabled(name: str) -> Optional[bool]:
    """Resolve the flag surface for one kernel: False = off (use the XLA
    fallback), True = forced on (the kernel's own validation raises on
    unsupported shapes — loud, per the flash-attention contract), None =
    auto (TPU backend + the kernel's shape gate decide)."""
    if name not in KERNEL_NAMES:
        raise ValueError(f"unknown pallas kernel {name!r}; "
                         f"known: {list(KERNEL_NAMES)}")
    from hetu_tpu.utils import flags
    mode = flags.str_flag("HETU_TPU_PALLAS")
    if mode == "0":
        return False
    if name not in _selected_kernels():
        return False
    if mode == "1":
        return True
    return None


# -- where a Mosaic call can lower ---------------------------------------
# The TPU lowering refuses a Mosaic call inside a program GSPMD partitions
# ("Mosaic kernels cannot be automatically partitioned"): it needs a
# one-device program or a shard_map region with EVERY mesh axis manual.
# Under a multi-device mesh the dispatchers therefore run the kernel once
# per shard (`per_shard`), on the layouts their caller declares.

def _open_axes():
    """(mesh, its axes no shard_map has made manual yet) where we are
    tracing — (None, ()) in a one-device program and inside an all-manual
    region, where a Mosaic call lowers as it stands."""
    import jax
    from hetu_tpu.core.mesh import current_mesh
    am = jax.sharding.get_abstract_mesh()
    if am.manual_axes:          # inside a shard_map: nest over the rest
        rest = tuple(a for a in am.axis_names if a not in am.manual_axes)
        return (am, rest) if rest else (None, ())
    mesh = current_mesh()
    if mesh is None or mesh.size == 1:
        return None, ()
    return mesh, tuple(mesh.axis_names)


def _shard_shape(shape, layout, mesh, axes) -> Tuple[int, ...]:
    """One shard's shape of a `shape` array laid out as `layout` (a
    DistributedStates; None = replicated) over the open `axes`."""
    if layout is None:
        return shape
    out = []
    for n, dim_axes in zip(shape, layout.spec):
        ways = 1
        for a in dim_axes:
            if a in axes:
                ways *= int(mesh.shape[a])
        if n % ways:
            raise ValueError(f"a dim of {n} does not split {ways} ways "
                             f"over mesh axes {dim_axes}")
        out.append(n // ways)
    return tuple(out)


def per_shard(fn, in_layouts, out_layouts):
    """`fn` — a function of arrays that launches a Mosaic kernel — run
    once per shard under a multi-device mesh: a shard_map over every open
    mesh axis, with the declared layouts (a DistributedStates per operand
    and per result, None = replicated) as its specs.  The kernels are
    per-token, per-head or elementwise over the sharded dims, so the
    shards need no exchange; the cotangent of a replicated operand (a
    norm gain) is summed over the mesh by shard_map's transpose.

    `fn` itself in a one-device program and in an all-manual region —
    and where the caller declared no layouts (`in_layouts=None`): only a
    forced flag routes a kernel there, and the TPU lowering then refuses
    it in its own words."""
    mesh, axes = _open_axes()
    if not axes or in_layouts is None:
        return fn
    import jax
    from jax.sharding import PartitionSpec as P
    from hetu_tpu.dstates import suppress_constraints

    def spec(layout):
        if isinstance(layout, tuple):
            return tuple(spec(l) for l in layout)
        return P() if layout is None else layout.partition_spec()

    def local(*args):
        with suppress_constraints():     # vacuous and illegal when manual
            return fn(*args)
    return jax.shard_map(local, mesh=mesh, in_specs=spec(tuple(in_layouts)),
                         out_specs=spec(out_layouts),
                         axis_names=frozenset(axes), check_vma=False)


# -- the routes a traced program took --------------------------------------

_routes = threading.local()


@contextlib.contextmanager
def record_routes(into: Optional[dict] = None):
    """Collect every `resolve_route` decision this thread takes inside the
    block — i.e. while a program is traced — as
    {kernel: {"pallas": n, "xla": n, "why": {reason: n}}}.  The Trainer
    and the ServingEngine keep this as `kernel_routes` and put it on their
    compile events: which kernels a program really runs is a fact of the
    run, not something to re-derive."""
    log = {} if into is None else into
    prev = getattr(_routes, "log", None)
    _routes.log = log
    try:
        yield log
    finally:
        _routes.log = prev


def _note_route(name: str, routed: bool, why: str):
    log, _routes.last = getattr(_routes, "log", None), None
    if log is None:
        return
    rec = log.setdefault(name, {"pallas": 0, "xla": 0, "why": {}})
    rec["pallas" if routed else "xla"] += 1
    rec["why"][why] = rec["why"].get(why, 0) + 1
    if routed:
        _routes.last = (name, rec["why"], why)


def _note_engagement(name: str, how: str):
    """A routed kernel's wrapper says HOW it engages: what its own rule
    chose from operands the gate never sees (a pool's item size), so that
    the reason `resolve_route` has just recorded for this call reads
    "shape gate passes, pages_per_block=4".  With no decision for `name`
    pending (a call past the dispatcher, or outside `record_routes`) it
    does nothing."""
    last, _routes.last = getattr(_routes, "last", None), None
    if last is None or last[0] != name:
        return
    _, reasons, why = last
    reasons[why] -= 1
    if not reasons[why]:
        del reasons[why]
    told = f"{why}, {how}"
    reasons[told] = reasons.get(told, 0) + 1


def resolve_route(name: str, check, *shapes, layouts=None, **kw) -> bool:
    """The one routing rule.  `check(*shapes, **kw)` is the kernel
    module's own entry validation (it raises ValueError with the reason);
    `shapes` are the operands' GLOBAL shapes and `layouts` how the caller
    lays them out over the mesh (one DistributedStates or None =
    replicated per shape; `layouts=None` = not declared) — the gate is
    asked about ONE SHARD's shapes, which is what the kernel will see.

    Forced flags win.  Auto takes the kernel on a TPU backend when the
    gate passes and the call can lower: in a one-device program, an
    all-manual region, or — through `per_shard` — under a multi-device
    mesh whose caller declared the layouts.  A kernel that then fails to
    compile is an error, never a route."""
    en = kernel_enabled(name)
    if en is not None:
        routed = en
        why = ("forced on by HETU_TPU_PALLAS=1" if en else
               "switched off by HETU_TPU_PALLAS / HETU_TPU_PALLAS_KERNELS")
    else:
        import jax
        mesh, axes = _open_axes()
        routed = False
        if jax.default_backend() != "tpu":
            why = "not a TPU backend"
        elif axes and layouts is None:
            why = ("multi-device mesh and the caller declares no layout: "
                   "a Mosaic call has to run per shard")
        else:
            try:
                if axes:
                    shapes = tuple(_shard_shape(s, l, mesh, axes)
                                   for s, l in zip(shapes, layouts))
                check(*shapes, **kw)
                routed, why = True, "shape gate passes"
            except ValueError as e:
                why = f"shape gate: {e}"
    _note_route(name, routed, why)
    return routed
