"""Multi-task LoRA fine-tuning demo (the LoBRA flow; reference:
examples/lobra — multi-task adapters over one frozen base with a batch
scheduler and per-task resource planner).

Two tasks share one frozen tiny-LLaMA base; the quota planner splits each
round's token budget by task weight x backlog, the scheduler packs both
tasks' samples into static-shaped micros (cross-task fused leftovers), and
the engine updates only the owning task's adapters per micro.

Run:  python examples/multi_task_lora.py   (CPU-friendly)
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    import jax

    from hetu_tpu import optim
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
    from hetu_tpu.peft.lora import LoRAConfig, MultiLoRAManager
    from hetu_tpu.peft.multi_task import (MultiTaskSFTEngine,
                                          TaskQuotaPlanner,
                                          schedule_micro_batches)

    cfg = LlamaConfig.tiny(remat=False)
    model = LlamaLMHeadModel(cfg)
    base = model.init(jax.random.key(0))
    mgr = MultiLoRAManager(model, base, LoRAConfig(rank=8),
                           tasks=["chat", "code"])
    engine = MultiTaskSFTEngine(mgr, optim.AdamW(lr=1e-3))

    rng = np.random.default_rng(0)
    datasets = {
        0: [rng.integers(1, cfg.vocab_size, size=rng.integers(16, 48))
            .astype(np.int32) for _ in range(24)],          # "chat"
        1: [rng.integers(1, cfg.vocab_size, size=rng.integers(16, 48))
            .astype(np.int32) for _ in range(12)],          # "code"
    }
    planner = TaskQuotaPlanner(weights={0: 2.0, 1: 1.0}, round_tokens=4096)
    backlog = {t: sum(len(s) for s in ss) for t, ss in datasets.items()}
    print("round quotas (tokens):", planner.plan(backlog))

    micros = schedule_micro_batches(datasets, max_tokens=256,
                                    train_task_num=2, bucket_sizes=(32, 64))
    print(f"{len(micros)} micros; fused:",
          sum(1 for m in micros if len(m.task_ids()) > 1))
    for epoch in range(3):
        hist = engine.train(micros)
        losses = {t: round(float(np.mean(v)), 4) for t, v in hist.items()}
        print(f"epoch {epoch}: per-task mean loss {losses}")


if __name__ == "__main__":
    main()
