"""Budgeted embedding-compression scheduling demo (reference: tools/
EmbeddingMemoryCompression/methods/scheduler/ — method switching under a
target compress rate).

Sweeps a memory budget over a set of tables with skewed access
frequencies (hot tables resist compression), then trains a toy two-tower
objective across a MIGRATION: halfway through, the budget halves, tables
move to cheaper methods at the checkpoint boundary, and training
continues.

Run:  python examples/compression_scheduler.py   (CPU-friendly)
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from hetu_tpu.nn.compression_scheduler import (ScheduledEmbeddings,
                                                   TableSpec, plan_methods)

    tables = [
        TableSpec("user", 20000, 32, access_freq=0.6),
        TableSpec("item", 50000, 32, access_freq=0.3),
        TableSpec("context", 100000, 32, access_freq=0.1),
    ]
    dense_total = sum(t.num_embeddings * t.embedding_dim * 4
                      for t in tables)

    print("== budget sweep ==")
    for frac in (1.0, 0.5, 0.2, 0.05):
        plan = plan_methods(tables, dense_total * frac)
        total = sum(c.bytes for c in plan.values())
        mix = {n: c.method for n, c in plan.items()}
        print(f"budget {frac:4.0%}: {mix}  ({total / 1e6:.1f}MB)")

    print("\n== training across a migration ==")
    sched = ScheduledEmbeddings(tables, dense_total)
    key = jax.random.key(0)
    params = sched.init(key)
    w = jax.random.normal(jax.random.fold_in(key, 7), (64, 1)) * 0.1
    rng = np.random.default_rng(0)
    uids = jnp.asarray(rng.integers(0, 20000, 512))
    iids = jnp.asarray(rng.integers(0, 50000, 512))
    y = jnp.asarray(rng.normal(size=(512, 1)), jnp.float32)

    def loss_fn(params, w):
        f = jnp.concatenate([sched.lookup("user", params, uids),
                             sched.lookup("item", params, iids)], axis=-1)
        return jnp.mean((f @ w - y) ** 2)

    @jax.jit
    def step(params, w):
        l, g = jax.value_and_grad(loss_fn, argnums=(0, 1),
                                  allow_int=True)(params, w)
        params = jax.tree.map(
            lambda p, gr: p - 0.1 * gr.astype(p.dtype)
            if jnp.issubdtype(p.dtype, jnp.floating) else p, params, g[0])
        return params, w - 0.1 * g[1], l

    for i in range(30):
        params, w, l = step(params, w)
        if i % 10 == 0:
            print(f"step {i:3d}  loss {float(l):.4f}  "
                  f"mem {sched.memory() / 1e6:.1f}MB")

    print("-- checkpoint boundary: budget halves; migrating --")
    params, migrations = sched.replan(params, budget_bytes=dense_total / 3,
                                      key=jax.random.fold_in(key, 1))
    for m in migrations:
        print(f"  {m['table']}: {m['from']} -> {m['to']}")

    for i in range(30, 60):
        params, w, l = step(params, w)
        if i % 10 == 0:
            print(f"step {i:3d}  loss {float(l):.4f}  "
                  f"mem {sched.memory() / 1e6:.1f}MB")
    print("done — training continued across the migration")


if __name__ == "__main__":
    main()
