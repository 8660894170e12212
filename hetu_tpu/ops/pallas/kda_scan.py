"""The chunkwise gated delta rule (`ops/delta_rule.chunk_scan`) as one
kernel a layer: the grid runs over sequences and HEADS, a head's rows are
walked in order inside the launch, and that head's state stays in VMEM
from its first row to its last.

What the XLA composition pays and this does not: the 32 heads' state
(2 MB float32) read three times and written once for every block of 16
rows (~1 GB a layer for a chunk of 2,048), one launch for every fusion
of the 128 iterations of its `lax.scan`, the relayout of q, k, v, g and
beta to [blocks, heads, 16, d] before the walk and of o after it, and
A, B, T, U, W and `k_end` of all blocks in HBM.  Here the operands are
read where the projections left them (`[C, heads * d]`: a head's 128
columns are one lane-dense column block), o is written the same way,
and everything else lives a tile.

**The arithmetic is `chunk_scan`'s** (`ops/delta_rule.py`, module
docstring): float32, every matrix product `HIGHEST`, the unit lower
triangular system of a 16-row block solved by the nilpotent series,
which is exact only at that size.  The one freedom taken is the TILE:
the three products with the state (`[k exp G; q exp G] S`, `k_end^T u`)
are made for `TILE` = n x 16 rows at once, and the n diagonal blocks are
chained exactly inside the tile:

    (I + N_bd + N_off) u = beta (v - (k exp G) S_0)
    (I + N_bd + N_off) = (I + M) (I + N_bd),   M = N_off (I + N_bd)^-1

`N_bd` holds the n 16-row diagonal blocks (inverted as `chunk_scan`
inverts them, all at once: powers of a block-diagonal matrix are the
blocks' powers), `N_off` the blocks under them.  M is strictly BLOCK
lower triangular, so M^n = 0 and (I + M)^-1 = prod_j (I + (-M)^(2^j)) has
log2(n) factors: no power of M beyond the (n - 1)th is ever formed, where
the series of a whole 128-row triangle needs the 127th and cancels
(PERF.md s6, PR 41).

`exp(G_i - G_j)` for j in an EARLIER 16-row block than i is formed
relative to the start of i's block: `exp(G_i - G_a0) exp(G_a0 - G_j)`,
both exponents <= 0, so the tile's size never meets float32's range;
inside a diagonal block it is `exp(G_i) exp(-G_j)` with the second
exponent clamped at 16 |g_floor| as in `chunk_scan`, which is what the
`g_floor` rule of `check_shapes` guards.

**Where the time goes** (my chip run, PR 42, one layer of the Ling
cell's chunk alone: 2.3-2.8 ms for the composition's 3.7): the inverse
is three fifths of it (the blocks' series 0.7 ms, the chain over the
blocks 0.7), the walk a fifth, and with every product at one bfloat16
pass it would take 1.3: the MXU's float32 passes over [128, 128]
operands bound it, not bytes and not the walk's latency.

**The unit norm of q and k** (`qk_scale`).  A head's q and k are scaled
to unit length over its 128 lanes, which the tile has loaded anyway.
Made before the call, the sum needs [C, heads, 128], and XLA pays for
[C, heads, 128] -> [C, heads * 128] in a tiled layout: as first built
the kernel took 15.8 ms out of the Ling chunk program's scan and the
producers of q, k, v put 5.7 back; with the norm in here SiLU's output
is split by columns in one fusion and nothing is relaid (my chip runs,
PR 42: 91.8 -> 79.6 ms a chunk program; the kernel 2.78 -> 2.83 ms).

**Padding.**  `valid` [b] (scalar prefetch) is how many of a sequence's
rows are its own: the kernel sets g = 0 and beta = 0 on the rows past
them (they leave the state as they find it) and writes zeros for their
o; a grid step whose rows all lie past `valid` computes nothing, reads
nothing new (its block index names the last live block again) and
passes the state through.

Shape contract (`check_shapes`): S [b, h, dk, dv] float32 with dk = dv
a multiple of 128, q, k, g [b, C, h * dk] and v [b, C, h * dv], beta
[b, C, h], C a multiple of `TILE`, 16 |g_floor| <= 87.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# the diagonal block (the size at which the triangular system is exact)
# and the unit norm's epsilon are the composition's
from hetu_tpu.ops.delta_rule import BLOCK as SUB, UNIT_EPS
from hetu_tpu.ops.pallas import _interpret

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST

#: rows whose products with the state are made at once, and rows a grid
#: step walks (whole tiles in one basic block, so that a tile's products
#: that do not wait for the state overlap the walk of the tile before).
#: Both from the sweep on the chip at the Ling cell's shape (a layer's
#: 2,048 rows; tile / rows: 16 / 128 4.94 ms, 32 / 256 3.69, 64 / 256
#: 3.00, 128 / 128 2.86, 128 / 256 2.78, 128 / 512 2.75: PERF.md s6,
#: PR 42); at 128 every [tile, tile] operand fills the MXU
TILE = 128
ROWS = 256


def check_shapes(s_shape, q_shape, v_shape, beta_shape, *,
                 g_floor: float, state_dtype=F32):
    """-> (b, C, h, d)."""
    if len(s_shape) != 4 or len(q_shape) != 3 or len(v_shape) != 3 \
            or len(beta_shape) != 3:
        raise ValueError(
            f"expected S [b, h, dk, dv], q [b, C, h * dk], v [b, C, h * dv]"
            f" and beta [b, C, h], got {s_shape} / {q_shape} / {v_shape} / "
            f"{beta_shape}")
    b, h, dk, dv = s_shape
    C = q_shape[1]
    if dk != dv or dk % 128:
        raise ValueError(f"a head's state {dk} x {dv} must be square and a "
                         f"multiple of 128 lanes")
    if tuple(q_shape) != (b, C, h * dk) or tuple(v_shape) != (b, C, h * dv) \
            or tuple(beta_shape) != (b, C, h):
        raise ValueError(
            f"q {q_shape} / v {v_shape} / beta {beta_shape} do not match a "
            f"state of {s_shape}")
    if C == 0 or C % TILE:
        raise ValueError(f"{C} rows are not a multiple of the kernel's tile "
                         f"of {TILE}")
    if jnp.dtype(state_dtype) != F32:
        raise ValueError(f"the state is {jnp.dtype(state_dtype).name}, the "
                         f"kernel keeps it float32")
    if SUB * abs(g_floor) > 87.0:
        raise ValueError(f"blocks of {SUB} positions at decays down to "
                         f"{g_floor} leave float32's range")
    return b, C, h, dk


def compatible(s_shape, q_shape, v_shape, beta_shape, **kw) -> bool:
    try:
        check_shapes(s_shape, q_shape, v_shape, beta_shape, **kw)
        return True
    except ValueError:
        return False


def _mm(a, b, dims=(((1,), (0,)), ((), ()))):
    return lax.dot_general(a, b, dims, precision=HIGHEST,
                           preferred_element_type=F32)


_NT = (((1,), (1,)), ((), ()))      # a b^T
_TN = (((0,), (0,)), ((), ()))      # a^T b


def _tile_masks(R: int):
    """The [R, R] masks of a tile of n = R / 16 diagonal blocks."""
    i = lax.broadcasted_iota(jnp.int32, (R, R), 0)
    j = lax.broadcasted_iota(jnp.int32, (R, R), 1)
    same = (i // SUB) == (j // SUB)
    return dict(eye=(i == j).astype(F32),
                strict=same & (j < i),     # under a block's diagonal
                lower=same & (j <= i),     # ... and on it
                row=lax.broadcasted_iota(jnp.int32, (R, 1), 0))


def _block_cumsum(g, row):
    """Running sums of g [R, dk] down the rows of each 16-row block
    (log-step, on the VPU: sums of g alone are no matrix product)."""
    at = row % SUB
    s = 1
    while s < SUB:
        g = g + jnp.where(at >= s, pltpu.roll(g, s, 0), 0.0)
        s *= 2
    return g


def _tile(St, q, k, v, g, beta, live, m, *, cap, qk_scale):
    """One tile of R rows of one head.  St [dv, dk] the state TRANSPOSED
    (its decay then scales lanes); q, k, g [R, dk], v [R, dv], beta and
    live [R, 1].  -> (o [R, dv], St').  Products that share a right-hand
    operand are made as one (the left-hand ones stacked): the MXU loads
    it once."""
    R = q.shape[0]
    n = R // SUB
    if qk_scale is not None:               # delta_rule.unit_length
        unit = lambda x: x * lax.rsqrt(  # noqa: E731
            jnp.sum(x * x, axis=1, keepdims=True) + UNIT_EPS)
        q, k = unit(q) * qk_scale, unit(k)
    g = jnp.where(live, g, 0.0)
    beta = jnp.where(live, beta, 0.0)
    Gl = _block_cumsum(g, m["row"])        # sum of g inside the block
    # ... and from the tile's start to each block's start
    starts = [jnp.zeros_like(Gl[:1])]
    for a in range(1, n):
        starts.append(starts[-1] + Gl[a * SUB - 1: a * SUB])
    Gt = Gl + jnp.concatenate(
        [jnp.broadcast_to(x, (SUB, x.shape[1])) for x in starts], axis=0)
    e_in = jnp.exp(Gl)
    lhs = jnp.concatenate([k * e_in, q * e_in], axis=0)        # [2R, dk]
    ab = _mm(lhs, k * jnp.exp(jnp.minimum(-Gl, cap)), _NT)     # [2R, R]
    N = jnp.where(m["strict"], ab[:R], 0.0) * beta
    B = jnp.where(m["lower"], ab[R:], 0.0)
    # (I + N_bd)^-1 = (I + P)(I + P^2)(I + P^4)(I + P^8), P = -N_bd: the
    # series of a nilpotent block (delta_rule._unit_lower_inverse) for
    # the n blocks at once
    P = -N
    P2 = _mm(P, P)
    x = _mm(jnp.concatenate([P, P2], axis=0), P2)              # P^3, P^4
    T, P4 = m["eye"] + P + P2 + x[:R], x[R:]
    x = _mm(jnp.concatenate([T, P4], axis=0), P4)
    T = T + x[:R]
    T = T + _mm(T, x[R:])
    a_off, b_off = [jnp.zeros((SUB, R), F32)], [jnp.zeros((SUB, R), F32)]
    for a in range(1, n):
        # rows before block a, decayed up to the block's start
        at = a * SUB
        before = jnp.where(
            m["row"] < at, k * jnp.exp(jnp.minimum(starts[a] - Gt, 0.0)), 0.0)
        x = _mm(jnp.concatenate([lhs[at: at + SUB],
                                 lhs[R + at: R + at + SUB]], axis=0),
                before, _NT)                                   # [32, R]
        a_off.append(x[:SUB])
        b_off.append(x[SUB:])
    B = B + jnp.concatenate(b_off, axis=0)
    # (I + N_bd + N_off)^-1 = T (I + M)^-1, M = N_off T strictly BLOCK
    # lower: (I + M)^-1 = prod (I + (-M)^(2^j)), 2^j < n
    P = -_mm(jnp.concatenate(a_off, axis=0) * beta, T)
    p = 1
    while p < n:
        if 2 * p < n:
            x = _mm(jnp.concatenate([T, P], axis=0), P)
            T, P = T + x[:R], x[R:]
        else:
            T = T + _mm(T, P)
        p *= 2
    e_t = jnp.exp(Gt)
    kq = _mm(jnp.concatenate([k * e_t, q * e_t], axis=0), St, _NT)
    u = _mm(T, beta * (v - kq[:R]))                            # [R, dv]
    o = kq[R:] + _mm(B, u)
    end = Gt[R - 1: R]                                         # [1, dk]
    St = St * jnp.exp(end) + _mm(u, k * jnp.exp(end - Gt), _TN)
    return jnp.where(live, o, 0.0), St


def _kernel(valid_ref, s0_ref, q_ref, k_ref, v_ref, g_ref, beta_ref,
            o_ref, s_ref, st_scr, *, rows, cap, qk_scale):
    bi, h, r = pl.program_id(0), pl.program_id(1), pl.program_id(2)

    @pl.when(r == 0)
    def _load():
        st_scr[...] = s0_ref[0, 0].T

    valid = valid_ref[bi]
    row0 = r * rows

    @pl.when(row0 < valid)
    def _walk():
        m = _tile_masks(TILE)
        lane = lax.broadcasted_iota(jnp.int32, (TILE, beta_ref.shape[2]), 1)
        St = st_scr[...]
        for lo in range(0, rows, TILE):
            at = slice(lo, lo + TILE)
            # this head's column of beta [rows, heads]
            beta = jnp.sum(jnp.where(lane == h, beta_ref[0, at], 0.0),
                           axis=1, keepdims=True)
            o, St = _tile(St, q_ref[0, at], k_ref[0, at], v_ref[0, at],
                          g_ref[0, at], beta, row0 + lo + m["row"] < valid, m,
                          cap=cap, qk_scale=qk_scale)
            o_ref[0, at] = o
        st_scr[...] = St

    @pl.when(row0 >= valid)
    def _padding():
        o_ref[...] = jnp.zeros_like(o_ref)

    @pl.when(r == pl.num_programs(2) - 1)
    def _store():
        s_ref[0, 0] = st_scr[...].T


def kda_scan(S, q, k, v, g, beta, valid, *, g_floor: float,
             qk_scale=None, rows: int = ROWS):
    """S [b, h, dk, dv] float32; q, k, g [b, C, h * dk], v [b, C, h * dv]
    and beta [b, C, h] float32; valid [b] int32.  -> (o [b, C, h * dv]
    with zeros past `valid`, S after the last valid row); with
    `qk_scale`, q and k are scaled to unit length a head and q by it
    first (`delta_rule.chunk_scan`); a grid step
    walks `rows` rows where they divide C, else a tile.  Raises ValueError
    on shapes outside `check_shapes`."""
    b, C, h, d = check_shapes(S.shape, q.shape, v.shape, beta.shape,
                              g_floor=g_floor, state_dtype=S.dtype)
    if C % rows:
        rows = TILE

    def rows_at(bi, hi, r, valid):
        # past the last live block: name it again (no DMA)
        return bi, jnp.minimum(r, jnp.maximum(valid[bi] - 1, 0) // rows), hi

    cols = pl.BlockSpec((1, rows, d), rows_at)
    state = pl.BlockSpec((1, 1, d, d), lambda bi, hi, r, _: (bi, hi, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, h, C // rows),
        in_specs=[state, cols, cols, cols, cols,
                  pl.BlockSpec((1, rows, h),
                               lambda bi, hi, r, valid:
                               rows_at(bi, 0, r, valid))],
        out_specs=[pl.BlockSpec((1, rows, d),
                                lambda bi, hi, r, _: (bi, r, hi)), state],
        scratch_shapes=[pltpu.VMEM((d, d), F32)],
    )
    o, S = pl.pallas_call(
        functools.partial(_kernel, rows=rows, cap=SUB * abs(g_floor),
                          qk_scale=qk_scale),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, C, h * d), F32),
                   jax.ShapeDtypeStruct(S.shape, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=_interpret(),
        name="pallas_kda_scan",
    )(valid.astype(jnp.int32), S, q, k, v, g, beta)
    return o, S
