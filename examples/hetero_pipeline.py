"""Heterogeneous pipeline demo: a Malleus straggler plan executed as ONE
program with per-stage TP degrees (reference: the Malleus/Ampelos line —
python/hetu/engine/strategy.py planners + distributed_states.h:158 unequal
stage groups).

Flow: measured per-device speeds -> AmpelosPlanner picks (tp, stage
layers) -> the plan becomes a ParallelStrategy with pp_tp_eff + uneven
pipeline_stage_layers -> validate() checks it against the engine envelope
-> Trainer runs it (GPipe or 1f1b; SP on).

Run:  XLA_FLAGS=--xla_force_host_platform_device_count=8 \
      JAX_PLATFORMS=cpu python examples/hetero_pipeline.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    import jax

    from hetu_tpu.core.mesh import MeshConfig
    from hetu_tpu.data import pad_batch
    from hetu_tpu.engine import Trainer, TrainingConfig
    from hetu_tpu.engine.ampelos import AmpelosPlanner
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
    from hetu_tpu.parallel import ParallelStrategy

    # measured relative speeds: devices 4-7 are straggling at 50%
    speeds = [1.0, 1.0, 1.0, 1.0, 0.5, 0.5, 0.5, 0.5]
    plan = AmpelosPlanner(num_layers=4, tp_candidates=(1, 2)).plan(speeds)
    stage_layers = tuple(s["layers"][1] - s["layers"][0]
                         for s in plan["stages"])
    tp = plan["strategy"]["tp"]
    pp = len(stage_layers)
    print(f"Ampelos plan: tp={tp} pp={pp} stage_layers={stage_layers} "
          f"(score {plan['score']})")

    # execute the plan: fast stages keep full TP, straggler stages run at
    # a reduced effective degree — read straight off the plan's per-stage
    # speeds (MalleusPlanner groups similar speeds into stages)
    pp_tp_eff = None
    if tp > 1:
        pp_tp_eff = tuple(tp if s["speed"] >= 1.0 else max(tp // 2, 1)
                          for s in plan["stages"])
    cfg = LlamaConfig.tiny(num_hidden_layers=sum(stage_layers),
                           pipeline_stage_layers=stage_layers, remat=True)
    st = ParallelStrategy(mesh=MeshConfig(dp=8 // (tp * pp), tp=tp, pp=pp),
                          pp_tp_eff=pp_tp_eff,
                          sequence_parallel=tp > 1, zero=True)
    tc = TrainingConfig(global_batch_size=8, micro_batch_size=2, seq_len=64,
                        lr=3e-3, warmup_steps=2, total_steps=20,
                        log_every=100)
    # the plan-time chokepoint: a plan outside the engine envelope fails
    # HERE with a named error, not at trace time
    st.validate(cfg, n_micro=tc.num_micro_batches(st.dp),
                global_batch=tc.global_batch_size, seq_len=tc.seq_len)

    model = LlamaLMHeadModel(cfg, st)
    tr = Trainer(model, tc, st).build()
    rng = np.random.default_rng(0)
    batch = pad_batch([rng.integers(1, 250, size=60) for _ in range(8)], 64)
    for i in range(6):
        m = tr.train_step(batch)
        if i % 2 == 0:
            print(f"step {i}  loss {float(m['loss']):.4f}  "
                  f"({st.describe()})")
    print("hetero pipeline trained — one program, per-stage TP degrees")


if __name__ == "__main__":
    main()
