"""The selective scan of a Mamba-1 layer (`ops/selective_scan.chunk_scan`)
as one kernel a layer: the grid runs over sequences, blocks of positions
and blocks of CHANNELS, a sequence's positions in order, and the state
`[N, D]` float32 stays in VMEM from the chunk's first row to its last;
it is read from HBM once and written once.

What the XLA composition pays and this does not: a `lax.scan` of s / 16
trips whose carry is the whole state, every trip its own fusions, u',
Delta, B and C moved to `[s / 16, 16, b, ...]` before the walk and y back
after it.  Here u' and Delta are read where `MambaMixer._inputs` leaves
them (`[b, s, D]`: a block of channels is a block of lanes) and y is
written the same way; only B and C are turned before the call
(`[b, s, N]` -> `[b, N, s]` float32, 32 KB each at the cells' shape: a
convert that XLA fuses, no copy).

**The arithmetic is `recurrence`'s** (`ops/selective_scan.py`), float32
throughout, position by position:

    h_t = exp(Delta_t A) * h_{t-1} + (Delta_t u_t) B_t^T
    y_t = C_t^T h_t + D * u_t

`exp(Delta_t A)` is taken as `2^(Delta_t (A log2 e))`: the chip's own
exponential IS a power of two of the argument times log2 e, and that
product is made once a channel block instead of once a position (same
function, same unit, one rounding of the argument either way).

The recurrence is diagonal (a decay a channel and lane), so there is no
matrix product in it: four multiplications, an addition and a half, one
exponential and its pop a vreg of state a position, all on the vector
unit, which the walk keeps 80% full (the bundle dump: 503 operations in
the 154 bundles of 8 positions of 512 lanes, 4 slots a bundle).
Everything else is arranged so that the walk does nothing more:

* **The state of `SUB` lanes is held in registers** across the positions
  of a grid step (`[N, SUB]` = 8 vregs at N = 16 beside A's 8), the
  positions walked by a `fori_loop` of 8 positions a trip (16 a trip
  read 7% faster alone and traced twice as long: `setup_s` is judged).
* **B_t and C_t across the lanes.**  h's rows are state lanes n, so B_t
  and C_t multiply sublanes: `[N, 1]` columns laid across 128 lanes.
  That is work for the lane-shuffle unit (~6 cycles a position and
  operand), so a block of positions lays its columns out ONCE
  (`_lay_columns`, into `[rows, N, 128]` scratch, when the block's first
  channel block runs) and every channel block reads them from there:
  that is why the position blocks are the OUTER grid axis and the whole
  state a scratch.
* **Delta_t and Delta_t u_t across the sublanes** cost nothing: a row of
  a `[8, 128]` tile is loaded with a sublane stride of 0.  The tiles
  (Delta with the padding masked, Delta u) are made 16 rows at a time
  before the walk and kept as tiles of their own.
* **y without a reduction a position.**  `C_t^T h_t` sums over the
  sublanes.  A position only ADDS its vregs down to `[8, 128]` a lane
  group and stores that; the sum over the 8 sublanes is made for 8
  positions at once, as 8 strided loads (sublane k of each position) and
  7 adds, which leaves y a dense tile added to the skip term.

**Padding.**  `valid` [b] (scalar prefetch) is how many of a sequence's
rows are its own.  Delta is 0 past them (exp(0) = 1 and nothing is
added: the composition's own rule) and the walk stops at the trip that
holds the last of them, so rows past it cost nothing; their y is `D u_t`
or zeros (finite, not the sequence's).  A grid step whose rows all lie
past `valid` computes nothing, reads nothing new (its block index names
the last live block again) and writes zeros.

**Where the time goes** (my chip runs, PR 52, one layer at the cells'
shape alone: 0.0925 ms for the composition's 0.323; 0.0755 at `valid`
384, 0.0680 at 256): the walk ~0.066 (98.6k bundles at 1.5 GHz, bound by
the vector unit's slots), the tiles before it and the columns ~0.01
each, the rest the grid's steps and the first and last block's DMA; the
bytes' floor is 0.033.

Shape contract (`check_shapes`): h [b, N, D] float32 with N a multiple
of 8, u and Delta [b, s, D], B and C [b, s, N], D and s multiples of
128.  Block sizes are the module's where they divide the shape, else the
largest multiple of 128 under them that does.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from hetu_tpu.ops.pallas import _interpret
from hetu_tpu.ops.pallas.flash_attention import fit_block

F32 = jnp.float32

#: lanes whose state a walk holds in registers; channels and positions a
#: grid step takes.  From the sweeps on the chip at the cells' shape
#: (PERF.md s6, PR 52).  Positions x channels, ms a layer at `valid` 512 /
#: 256: 256 x 1024 0.0925 / 0.0680, 128 x 2560 0.0924 / 0.0677, 256 x 512
#: 0.0937 / 0.0688, 512 x 1024 0.0962 / 0.0630, 512 x 5120 0.1227 / 0.0874.
#: Lanes in registers (the first tree, 256 x 1024): 256 0.1078, 512 0.0880,
#: 1024 0.0845; 16 positions a loop trip 0.0820 for 8's 0.0880
SUB = 512
DBLK = 1024
ROWS = 256
#: a vreg of float32 is [TILE, LANES]; a tile of bfloat16 is PACK rows
TILE, LANES, PACK = 8, 128, 16
#: positions whose columns `_lay_columns` lays out a loop trip
GROUP = 32
LOG2E = 1.4426950408889634
#: the scoped VMEM a launch may take: ~13 MB at the module's blocks (the
#: blocks twice, the tiles, the columns and the state), ~50 MB at the
#: sweep's largest
VMEM_LIMIT = 64 * 1024 * 1024


def check_shapes(h_shape, u_shape, b_shape, *, state_dtype=F32):
    """-> (b, s, N, D)."""
    if len(h_shape) != 3 or len(u_shape) != 3 or len(b_shape) != 3:
        raise ValueError(
            f"expected h [b, N, D], u and Delta [b, s, D] and B, C "
            f"[b, s, N], got {h_shape} / {u_shape} / {b_shape}")
    b, N, D = h_shape
    s = u_shape[1]
    if tuple(u_shape) != (b, s, D) or tuple(b_shape) != (b, s, N):
        raise ValueError(f"u {u_shape} / B {b_shape} do not match a state "
                         f"of {h_shape}")
    if N == 0 or N % 8:
        raise ValueError(f"a state of {N} lanes a channel is not a "
                         f"multiple of 8 sublanes")
    if D == 0 or D % LANES:
        raise ValueError(f"{D} channels are not a multiple of {LANES} "
                         f"lanes")
    if s == 0 or s % LANES:
        raise ValueError(f"{s} rows are not a multiple of the kernel's "
                         f"{LANES} positions")
    if jnp.dtype(state_dtype) != F32:
        raise ValueError(f"the state is {jnp.dtype(state_dtype).name}, the "
                         f"kernel keeps it float32")
    return b, s, N, D


def _lay_columns(xT_ref, out_scr, rows):
    """xT_ref [1, N, rows] (a column a position) -> out_scr [rows, N,
    128]: position t's column across the lanes."""
    N = xT_ref.shape[1]

    def tile(j, _):
        at = pl.multiple_of(j * LANES, LANES)
        x = xT_ref[0, :, pl.ds(at, LANES)]                   # [N, 128]

        def columns(c, _):
            # the group's lanes turned to the first lanes (one rotate),
            # then each laid across the lanes from where it stands
            first = pl.multiple_of(c * GROUP, GROUP)
            xr = pltpu.roll(x, (LANES - first) % LANES, 1)
            for k in range(GROUP):
                out_scr[at + first + k] = jnp.broadcast_to(
                    xr[:, k: k + 1], (N, LANES))
            return 0
        lax.fori_loop(0, LANES // GROUP, columns, 0)
        return 0
    lax.fori_loop(0, rows // LANES, tile, 0)


def _kernel(valid_ref, h0_ref, u_ref, dt_ref, a_ref, bT_ref, cT_ref, d_ref,
            y_ref, h_ref, h_scr, bb_scr, cb_scr, dt_scr, du_scr, p_scr,
            *, rows, sub):
    bi, r, d = pl.program_id(0), pl.program_id(1), pl.program_id(2)
    _, N, dblk = h_scr.shape
    lane0 = pl.multiple_of(d * dblk, dblk)               # the step's channels
    here = pl.ds(lane0, dblk)

    @pl.when(r == 0)
    def _load():
        h_scr[d] = h0_ref[0, :, here]

    left = valid_ref[bi] - r * rows      # the step's rows that are live

    @pl.when(left > 0)
    def _walk():
        @pl.when(d == 0)
        def _columns():
            _lay_columns(bT_ref, bb_scr, rows)
            _lay_columns(cT_ref, cb_scr, rows)

        live = jnp.minimum(left, rows)
        skip = d_ref[:, here]                                    # [1, dblk]
        at16 = lax.broadcasted_iota(jnp.int32, (PACK, 1), 0)

        def prepare(c, _):
            # 16 rows (a tile of bfloat16) at once: Delta with the padding
            # masked, Delta u, and y begun with the skip term
            t0 = pl.multiple_of(c * PACK, PACK)
            u = u_ref[0, pl.ds(t0, PACK), :].astype(F32)
            dt = jnp.where(t0 + at16 < left, dt_ref[0, pl.ds(t0, PACK), :],
                           0.0)
            du = dt * u
            # as [8, 128] tiles of their own: a row of one is loaded
            # across the sublanes for free only from a ref whose rows ARE
            # 128 lanes (from a wider one Mosaic adds a permute a load)
            for k in range(PACK // TILE):
                for g in range(dblk // LANES):
                    at = (slice(k * TILE, (k + 1) * TILE),
                          slice(g * LANES, (g + 1) * LANES))
                    dt_scr[c * (PACK // TILE) + k, g] = dt[at]
                    du_scr[c * (PACK // TILE) + k, g] = du[at]
            y_ref[0, pl.ds(t0, PACK), :] = skip * u
            return 0
        packs = (live + PACK - 1) // PACK
        lax.fori_loop(0, packs, prepare, 0)

        def blank(c, _):
            y_ref[0, pl.ds(pl.multiple_of(c * PACK, PACK), PACK), :] = \
                jnp.zeros((PACK, dblk), F32)
            return 0
        lax.fori_loop(packs, rows // PACK, blank, 0)

        halves = range(0, N, TILE)       # a vreg of state: 8 lanes n
        for lo in range(0, dblk, sub):
            groups = range(lo, lo + sub, LANES)
            vregs = [(slice(n, n + TILE), slice(g, g + LANES))
                     for g in groups for n in halves]
            # exp(Delta A) = 2^(Delta (A log2 e)): the product with log2 e
            # that the chip's exp makes a position is made once here
            A2 = [a_ref[n, pl.ds(lane0 + g.start, LANES)] * LOG2E
                  for n, g in vregs]

            def walk(c, h):
                t0 = pl.multiple_of(c * TILE, TILE)
                h = list(h)
                for i in range(TILE):
                    Bb, Cb = bb_scr[t0 + i], cb_scr[t0 + i]       # [N, 128]
                    for j, g in enumerate(groups):
                        row = (c, g // LANES, slice(i, i + 1))
                        dt = jnp.broadcast_to(dt_scr[row], (TILE, LANES))
                        x = jnp.broadcast_to(du_scr[row], (TILE, LANES))
                        p = 0.0
                        for k, n in enumerate(halves):
                            at = j * len(halves) + k
                            h[at] = jnp.exp2(dt * A2[at]) * h[at] \
                                + x * Bb[n: n + TILE]
                            # the lane group's N / 8 vregs, added
                            p = p + h[at] * Cb[n: n + TILE]
                        p_scr[j, i * TILE: (i + 1) * TILE] = p
                for j, g in enumerate(groups):
                    # sublane k of each of the 8 positions
                    y = sum(p_scr.at[j][pl.ds(k, TILE, stride=TILE)]
                            for k in range(TILE))
                    y_ref[0, pl.ds(t0, TILE), g: g + LANES] += y
                return tuple(h)
            h = lax.fori_loop(0, (live + TILE - 1) // TILE, walk,
                              tuple(h_scr[(d, *at)] for at in vregs))
            for at, x in zip(vregs, h):
                h_scr[(d, *at)] = x

    @pl.when(left <= 0)
    def _padding():
        y_ref[...] = jnp.zeros_like(y_ref)

    @pl.when(r == pl.num_programs(1) - 1)
    def _store():
        h_ref[0, :, here] = h_scr[d]


def selective_scan(h, u, delta, A, B, C, D, valid, *, rows: int = ROWS,
                   dblk: int = DBLK):
    """h [b, N, D] float32; u [b, s, D] (any float type), delta [b, s, D]
    float32; A [N, D]; B, C [b, s, N]; D [D]; valid [b] int32.
    -> (y [b, s, D] float32, h after the last valid row).  Raises
    ValueError on shapes outside `check_shapes`."""
    check_shapes(h.shape, u.shape, B.shape, state_dtype=h.dtype)
    return _launch(h, u, delta, A, B, C, D, valid, rows=rows, dblk=dblk,
                   interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("rows", "dblk", "interpret"))
def _launch(h, u, delta, A, B, C, D, valid, *, rows, dblk, interpret):
    """Jitted, so that a program whose layer bodies call the scan at one
    shape (three in Jamba's chunk program) traces and lowers the kernel
    ONCE: its walk is a thousand operations written out, ~0.7 s a trace
    on a benchmark host, and `setup_s` is judged."""
    b, N, Dn = h.shape
    s = u.shape[1]
    # the largest multiples of 128 under them that divide the shape
    rows, dblk = fit_block(rows, s), fit_block(dblk, Dn)
    sub = fit_block(SUB, dblk)

    def last_live(bi, r, valid):
        # past the last live block: name it again (no DMA)
        return jnp.minimum(r, jnp.maximum(valid[bi] - 1, 0) // rows)

    cols = pl.BlockSpec((1, rows, dblk), lambda bi, r, d, valid: (
        bi, last_live(bi, r, valid), d))
    turned = pl.BlockSpec((1, N, rows), lambda bi, r, d, valid: (
        bi, 0, last_live(bi, r, valid)))
    state = pl.BlockSpec((1, N, Dn), lambda bi, r, d, _: (bi, 0, 0))
    tiles = (rows // TILE, dblk // LANES, TILE, LANES)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s // rows, Dn // dblk),
        in_specs=[state, cols, cols,
                  pl.BlockSpec((N, Dn), lambda bi, r, d, _: (0, 0)),
                  turned, turned,
                  pl.BlockSpec((1, Dn), lambda bi, r, d, _: (0, 0))],
        out_specs=[pl.BlockSpec((1, rows, dblk),
                                lambda bi, r, d, _: (bi, r, d)), state],
        scratch_shapes=[pltpu.VMEM((Dn // dblk, N, dblk), F32),
                        pltpu.VMEM((rows, N, LANES), F32),
                        pltpu.VMEM((rows, N, LANES), F32),
                        pltpu.VMEM(tiles, F32), pltpu.VMEM(tiles, F32),
                        pltpu.VMEM((sub // LANES, TILE * TILE, LANES), F32)],
    )
    turn = lambda x: jnp.swapaxes(x.astype(F32), 1, 2)  # noqa: E731
    y, h = pl.pallas_call(
        functools.partial(_kernel, rows=rows, sub=sub),
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct((b, s, Dn), F32),
                   jax.ShapeDtypeStruct(h.shape, F32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT),
        interpret=interpret,
        name="pallas_selective_scan",
    )(valid.astype(jnp.int32), h, u, delta.astype(F32), A.astype(F32),
      turn(B), turn(C), D.astype(F32)[None])
    return y, h
