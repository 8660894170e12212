"""Embedding-table compression demo (reference:
tools/EmbeddingMemoryCompression/run_compressed.py — train/infer CTR models
with compressed learnable vector storage).

Compares the method families on one table: storage, reconstruction error
(for post-hoc methods) and a short training run (for learnable methods) on
a toy two-tower CTR objective.

Run:  python examples/embedding_compression.py   (CPU-friendly)
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    import jax
    import jax.numpy as jnp

    from hetu_tpu.nn.embedding_compression import (DedupEmbedding,
                                                   HashEmbedding, QREmbedding,
                                                   QuantizedEmbedding,
                                                   TTEmbedding)

    V, D = 5000, 32
    rng = np.random.default_rng(0)
    table = jnp.asarray(rng.normal(0, 0.05, (V, D)), jnp.float32)
    ids = jnp.asarray(rng.integers(0, V, 4096), jnp.int32)
    ref = jnp.take(table, ids, axis=0)

    print(f"dense table: {V}x{D} fp32 = {V * D * 4 / 1e6:.1f} MB")

    # --- post-hoc compression of a trained table --------------------------
    for bits in (8, 4):
        emb = QuantizedEmbedding(V, D, bits=bits)
        p = emb.compress(table)
        err = float(jnp.max(jnp.abs(emb.lookup(p, ids) - ref)))
        print(f"quantize int{bits}: {emb.compression():.1f}x, "
              f"max err {err:.4f}")

    dedup = DedupEmbedding(V, D)
    p = dedup.compress(np.asarray(table), atol=5e-2)
    err = float(jnp.max(jnp.abs(dedup.lookup(p, ids) - ref)))
    print(f"dedup (atol=5e-2): {dedup.compression_of(p):.1f}x, "
          f"max err {err:.4f}")

    # --- learnable compressed tables (train on a toy CTR objective) ------
    y = jnp.asarray(rng.integers(0, 2, ids.shape[0]), jnp.float32)

    def train(emb, params, steps=30, lr=0.5):
        def loss(p):
            z = jnp.mean(emb.lookup(p, ids), axis=-1)
            return jnp.mean((jax.nn.sigmoid(z * 20) - y) ** 2)

        g = jax.jit(jax.grad(loss))
        for _ in range(steps):
            params = jax.tree.map(lambda p, d: p - lr * d, params, g(params))
        return float(loss(params))

    for name, emb in [
            ("hash x2", HashEmbedding(V, D, compressed_rows=V // 16)),
            ("QR mult", QREmbedding(V, D)),
            ("TT rank8", TTEmbedding(V, D, vocab_factors=(18, 18, 18),
                                     dim_factors=(4, 4, 2), rank=8))]:
        params = emb.init(jax.random.key(1))
        final = train(emb, params)
        print(f"{name}: {emb.compression():.1f}x, toy-CTR loss {final:.4f}")


if __name__ == "__main__":
    main()
