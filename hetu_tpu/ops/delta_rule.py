"""The gated delta rule with a decay per key channel (KDA): the
chunkwise form for a block of positions (prefill) and the
single-position update (decode).

Which entry runs what: `recurrence` and `step` are XLA compositions on
every backend (`step` is the decode program's; it stands at 71% of its
bytes' floor and has no kernel).  `chunk_scan` is the ONE entry of the
chunkwise form and asks `ops.pallas.resolve_route("kda_scan")`: on a TPU,
for the shapes its gate takes, `ops/pallas/kda_scan.py` walks a head's
blocks inside one launch with the state in VMEM; every other backend and
shape runs the XLA composition `_chunk_scan_xla` below, the form the
kernel is tested against.

Per head, state S in R^{dk x dv} (float32), per position t a query q_t and
a key k_t in R^dk, a value v_t in R^dv, a log-decay g_t in R^dk (<= 0) and
a write strength beta_t in [0, 1]:

    S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
    o_t = S_t^T q_t

`recurrence` is that, position by position (`lax.scan`): the definition
the other two are tested against.

**Chunkwise** (`chunk_scan`; as written here, `_chunk_scan_xla`).  With u_t = beta_t (v_t - (Diag(exp g_t)
S_{t-1})^T k_t) the step is S_t = Diag(exp g_t) S_{t-1} + k_t u_t^T, so
inside a block of L positions that enters with S_0, with G_i = sum_{j<=i}
g_j:

    (I + tril(A, -1)) u = beta (v - (k * exp G) S_0),
        A_ij = beta_i sum_d k_id k_jd exp(G_id - G_jd)
    o_i = (q_i * exp G_i) S_0 + sum_{j<=i} B_ij u_j,
        B_ij = sum_d q_id k_jd exp(G_id - G_jd)
    S_L = Diag(exp G_L) S_0 + sum_j (k_j * exp(G_L - G_j)) u_j^T

With T = (I + tril(A, -1))^-1, u = T beta v - (T beta (k * exp G)) S_0:
the two products with T do not depend on S_0 and are made for every
block at once, so that the walk over the blocks, the only part that is
sequential across blocks, is three matrix products a block.  A block is
`BLOCK` = 16 positions, for two reasons.  T is the power series of a
nilpotent matrix (`_unit_lower_inverse`), which survives neighbouring
keys that are alike only at that size.  And exp(G_i - G_j) is formed as
exp(G_i) exp(-G_j): the first factor's exponent lies in [-16 |g|max, 0],
the second's is clamped at +16 |g|max (it is larger only for j > i,
which the triangle drops), so with |g| < 5.5 neither leaves float32's
range (88); a decay bounded below is what makes that possible.

A position whose `beta` is 0 and `g` is 0 leaves the state as it found
it: that is how a chunk's padding rows (`chunk_scan`'s `valid`) and a
decode pass's idle slots (the caller of `step` sets them) are masked.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

F32 = jnp.float32
HIGHEST = lax.Precision.HIGHEST


def step(S, q, k, v, g, beta):
    """One position.  S [..., dk, dv] float32; q, k, g [..., dk]; v
    [..., dv]; beta [...].  -> (o [..., dv], S').  Written so that the
    state is read for two reductions over dk and once more for the
    update, and written once: o = q^T S' is taken from the decayed state
    and u, not from S'."""
    S = S.astype(F32)
    q, k, v, g, beta = (x.astype(F32) for x in (q, k, v, g, beta))
    Sd = S * jnp.exp(g)[..., None]
    kS = jnp.sum(Sd * k[..., None], axis=-2)
    qS = jnp.sum(Sd * q[..., None], axis=-2)
    u = beta[..., None] * (v - kS)
    o = qS + jnp.sum(q * k, axis=-1, keepdims=True) * u
    return o, Sd + k[..., None] * u[..., None, :]


#: under the root of `unit_length`
UNIT_EPS = 1e-6


def unit_length(x):
    """x [..., d] scaled to unit length along d (the QK norm a head)."""
    return x * lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + UNIT_EPS)


def recurrence(S, q, k, v, g, beta):
    """The definition: positions one after the other.  S [h, dk, dv];
    q, k, g [s, h, dk]; v [s, h, dv]; beta [s, h].
    -> (o [s, h, dv], S after the last position)."""
    def one(S, x):
        o, S = step(S, *x)
        return S, o
    S, o = lax.scan(one, S.astype(F32), (q, k, v, g, beta))
    return o, S


def _unit_lower_inverse(N):
    """(I + N)^-1 for strictly lower-triangular N [..., n, n]: N is
    nilpotent, so the inverse is sum_m (-N)^m = prod_k (I + (-N)^(2^k)),
    log2(n) squarings, all matrix products.  FOR SMALL n ONLY, which is
    why a block is 16 positions: the powers of N grow like binomials
    before they vanish, and the sum cancels; with neighbouring keys alike
    (a short convolution makes them so: k_i . k_{i+1} of 0.8-0.99) it is
    exact to 1e-5 at n = 16, off by 2-8% at 32 and without a finite value
    at 64 (tests/test_bailing_hybrid.py)."""
    n = N.shape[-1]
    eye = jnp.eye(n, dtype=N.dtype)
    P, T = -N, eye - N
    m = 2
    while m < n:
        P = jnp.matmul(P, P, precision=HIGHEST)
        T = jnp.matmul(T, eye + P, precision=HIGHEST)
        m *= 2
    return T


#: positions of a block of `chunk_scan`, and of a diagonal block of the
#: kernel (module docstring: the size at which the triangular system is
#: exact).  The benchmark's cost function divides by it.  PR 41's sweep
#: of the COMPOSITION (1.46 ms a layer for 1,024 rows in blocks of 16,
#: 1.72 in blocks of 64 solved in sub-blocks of 16) and PR 42's of the
#: kernel's tile are in PERF.md s6
BLOCK = 16


def chunk_scan(S, q, k, v, g, beta, *, g_floor: float = -5.0, valid=None,
               qk_scale=None):
    """`recurrence` over s positions in blocks of `BLOCK` (no g under
    `g_floor`).  Same arguments and results for one sequence, or a batch
    of them: a leading b on every argument.  `valid` (a count, [b] for a
    batch; default s): only a sequence's first `valid` positions are its
    own; the rest leave the state as it was and their o is zeros.
    `qk_scale` (default None: q and k come ready): q and k come as the
    activation left them and the scan takes `unit_length(q) * qk_scale`
    and `unit_length(k)` for them.  The norm is a sum over a head's 128
    lanes: made before the call, XLA needs q and k as [s, heads, 128]
    for it, and [s, heads, 128] -> [s, heads * 128] moves bytes in a
    TPU's tiled layout (5.7 ms a Ling chunk: PERF.md s6, PR 42); the
    kernel makes it on the columns it has already loaded.

    **The one entry, two routes** (`ops.pallas.resolve_route("kda_scan")`,
    recorded in `kernel_routes`): on a TPU, for shapes the kernel's gate
    takes (dk = dv a multiple of 128, s a multiple of its tile, float32
    state, `g_floor` inside the range rule), `ops/pallas/kda_scan.py`
    under the scope `pallas_kda_scan`; everywhere else the XLA
    composition below, which is also what the kernel is tested against.
    Both are float32 with every matrix product `HIGHEST` (six bfloat16
    passes on a TPU): one pass moves o and the state by 0.6% a call and
    six by 0.01% (my chip run, PR 41)."""
    from hetu_tpu.ops import pallas as _pl
    from hetu_tpu.ops.pallas import kda_scan as _ks
    one = q.ndim == 3
    if one:
        S, q, k, v, g, beta = (x[None] for x in (S, q, k, v, g, beta))
    b, s, h, dk = q.shape
    dv = v.shape[-1]
    valid = jnp.broadcast_to(
        jnp.asarray(s if valid is None else valid, jnp.int32), (b,))
    if _pl.resolve_route("kda_scan", _ks.check_shapes, S.shape,
                         (b, s, h * dk), (b, s, h * dv), beta.shape,
                         g_floor=g_floor, state_dtype=S.dtype):
        flat = lambda x: x.astype(F32).reshape(b, s, -1)  # noqa: E731
        with jax.named_scope("pallas_kda_scan"):
            o, S = _ks.kda_scan(S, flat(q), flat(k), flat(v), flat(g),
                                beta.astype(F32), valid, g_floor=g_floor,
                                qk_scale=qk_scale)
        o = o.reshape(b, s, h, dv)
    else:
        if qk_scale is not None:
            q, k = unit_length(q.astype(F32)) * qk_scale, unit_length(
                k.astype(F32))
        real = jnp.arange(s)[None, :] < valid[:, None]           # [b, s]
        g = jnp.where(real[..., None, None], g, 0.0)
        beta = jnp.where(real[..., None], beta, 0.0)
        o, S = jax.vmap(lambda *a: _chunk_scan_xla(*a, g_floor=g_floor))(
            S, q, k, v, g, beta)
        o = jnp.where(real[..., None, None], o, 0.0)
    return (o[0], S[0]) if one else (o, S)


def _chunk_scan_xla(S, q, k, v, g, beta, *, g_floor: float):
    """One sequence by the XLA composition (positions past the last
    whole block are padded with beta = 0, g = 0)."""
    s, h, dk = q.shape
    dv = v.shape[-1]
    cap = BLOCK * abs(g_floor)
    if cap > 87.0:
        raise ValueError(f"blocks of {BLOCK} positions at decays down to "
                         f"{g_floor} leave float32's range")
    nb = -(-s // BLOCK)
    # [blocks, heads, positions of the block, ...]
    to_blocks = lambda x: jnp.moveaxis(jnp.pad(  # noqa: E731
        x.astype(F32), ((0, nb * BLOCK - s),) + ((0, 0),) * (x.ndim - 1)
    ).reshape((nb, BLOCK) + x.shape[1:]), 1, 2)
    q, k, v, g, beta = map(to_blocks, (q, k, v, g, beta))
    G = jnp.cumsum(g, axis=2)                          # [nb, h, L, dk]
    decay = jnp.exp(G)                                 # exp G_i <= 1
    kc = k * jnp.exp(jnp.minimum(-G, cap))             # k_j exp(-G_j)
    qd = q * decay
    A = jnp.einsum("nhid,nhjd->nhij", k * decay, kc, precision=HIGHEST)
    B = jnp.einsum("nhid,nhjd->nhij", qd, kc, precision=HIGHEST)
    tri = jnp.tril(jnp.ones((BLOCK, BLOCK), bool))
    T = _unit_lower_inverse(
        jnp.where(tri & ~jnp.eye(BLOCK, dtype=bool), A, 0.0)
        * beta[..., None])
    B = jnp.where(tri, B, 0.0)
    mm = lambda a, b: jnp.matmul(a, b, precision=HIGHEST)  # noqa: E731
    # U = T beta v [.., L, dv] and W = T beta (k * exp G) [.., L, dk]
    UW = mm(T, beta[..., None] * jnp.concatenate([v, k * decay], axis=-1))
    U, W = UW[..., :dv], UW[..., dv:]
    k_end = k * jnp.exp(G[:, :, -1:] - G)              # k_j exp(G_L - G_j)
    end = decay[:, :, -1]                              # [nb, h, dk]

    def one(S, x):
        U, W, B, qd, k_end, end = x
        u = U - mm(W, S)                               # [h, L, dv]
        o = mm(qd, S) + mm(B, u)
        S = end[..., None] * S + jnp.einsum("hld,hlv->hdv", k_end, u,
                                            precision=HIGHEST)
        return S, o
    S, o = lax.scan(one, S.astype(F32), (U, W, B, qd, k_end, end))
    return jnp.moveaxis(o, 1, 2).reshape(nb * BLOCK, h, dv)[:s], S
