"""Heterogeneous data parallelism demo: a straggling half-cluster gets a
smaller batch share and a smaller tp degree, yet trains the SAME model in
lockstep with the fast half (reference: the Malleus workflow —
python/hetu/engine/strategy.py + hetero DS unions distributed_states.h:158).

Run (CPU virtual mesh):
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    JAX_PLATFORMS=cpu python examples/hetero_dp.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import numpy as np

from hetu_tpu import optim
from hetu_tpu.core.mesh import MeshConfig
from hetu_tpu.engine.malleus import StragglerProfile, plan_hetero_dp_shares
from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
from hetu_tpu.parallel import HeteroDPEngine, HeteroDPGroup, ParallelStrategy


def main():
    devs = jax.devices()
    assert len(devs) >= 8, "run with an 8-device mesh (see module docstring)"

    # 1. measure (or inject) per-device speeds; devices 4-7 are 2x slower
    profile = StragglerProfile([1.0] * 4 + [0.5] * 4)

    # 2. plan per-group batch rows proportional to group throughput
    total_rows = 16
    shares = plan_hetero_dp_shares(
        profile, [[0, 1, 2, 3], [4, 5, 6, 7]], [2, 1], total_rows)
    print(f"batch shares (fast/slow): {shares}")

    # 3. per-group strategies: the fast half runs dp2xtp2, the slow half tp4
    cfg = LlamaConfig.tiny(remat=False, num_key_value_heads=4)
    engine = HeteroDPEngine(
        lambda st: LlamaLMHeadModel(cfg, st), optim.AdamW(lr=3e-3),
        [HeteroDPGroup(ParallelStrategy(mesh=MeshConfig(dp=2, tp=2),
                                        zero=False), devs[:4], shares[0]),
         HeteroDPGroup(ParallelStrategy(mesh=MeshConfig(tp=4),
                                        zero=False), devs[4:8], shares[1])])
    engine.build()

    ids = np.random.default_rng(0).integers(
        1, 250, size=(total_rows, 64)).astype(np.int32)
    for step in range(10):
        m = engine.train_step({"input_ids": ids})
        if step % 3 == 0:
            print(f"step {step}: loss {m['loss']:.4f} "
                  f"({int(m['tokens'])} tokens)")
    print("done — both groups hold identical updated params")


if __name__ == "__main__":
    main()
