"""Micro-benchmark the flash-attention kernels on the real chip.

Chains REPS dependent kernel calls inside one jit so device time dominates
the per-dispatch latency. Used to A/B grid designs
(rectangular + pl.when skip vs compressed pair tables)."""
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPS = 16


def timeit(f, *args, iters=5):
    o = f(*args)
    np.asarray(jax.tree_util.tree_leaves(o)[0][0, 0])  # host fetch = sync
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        o = f(*args)
        np.asarray(jax.tree_util.tree_leaves(o)[0][0, 0])
        ts.append(time.perf_counter() - t0)
    return min(ts) / REPS


def main():
    from hetu_tpu.ops.pallas.flash_attention import flash_attention
    b, s, h, dh = 8, 2048, 12, 128
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.bfloat16)
    k = jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.bfloat16)
    v = jnp.asarray(rng.standard_normal((b, s, h, dh)), jnp.bfloat16)

    @jax.jit
    def fwd(q, k, v):
        def body(qq, _):
            o = flash_attention(qq, k, v, causal=True)
            return o, ()
        o, _ = jax.lax.scan(body, q, None, length=REPS)
        return o

    t_fwd = timeit(fwd, q, k, v)

    @jax.jit
    def fb(q, k, v):
        def body(qq, _):
            g = jax.grad(lambda x: flash_attention(
                x, k, v, causal=True).astype(jnp.float32).sum())(qq)
            return g.astype(qq.dtype), ()
        g, _ = jax.lax.scan(body, q, None, length=REPS)
        return g

    t_fb = timeit(fb, q, k, v)

    # causal attention matmul FLOPs: qk + pv fwd (x2 ops each), bwd adds
    # dv, dp, ds->dq, ds->dk (4 tile matmuls) => bwd = 2x fwd
    f_fwd = b * h * (2 * 2 * s * s * dh) / 2
    f_fb = f_fwd * 3
    peak = 197e12
    print(f"fwd  {t_fwd*1e3:8.2f} ms  {f_fwd/t_fwd/peak:.3f} of peak")
    print(f"f+b  {t_fb*1e3:8.2f} ms  {f_fb/t_fb/peak:.3f} of peak")


if __name__ == "__main__":
    sys.exit(main())
