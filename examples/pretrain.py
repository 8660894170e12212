"""LLaMA pretraining (reference: examples/pretrain/train_hetu.py).

    python examples/pretrain.py --ds-config ds.json --steps 100
    python examples/pretrain.py --dp 2 --tp 2 --sp --packing
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import argparse
import json

import numpy as np


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--config", help="experiment YAML (parallel/model/trainer "
                    "sections; see examples/config/)")
    ap.add_argument("--ds-config", help="ds-parallel JSON (planner output)")
    ap.add_argument("--dp", type=int, default=1)
    ap.add_argument("--tp", type=int, default=1)
    ap.add_argument("--pp", type=int, default=1)
    ap.add_argument("--cp", type=int, default=1)
    ap.add_argument("--sp", action="store_true")
    ap.add_argument("--model", default="tiny",
                    choices=["tiny", "llama2_7b", "llama2_13b", "llama3_8b"])
    ap.add_argument("--data", help=".jsonl with a 'text' field (synthetic "
                    "data when omitted)")
    ap.add_argument("--tokenizer", default="gpt2")
    ap.add_argument("--steps", type=int, default=None,
                help="override total steps (YAML/default 50)")
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--micro-batch", type=int, default=2)
    ap.add_argument("--seq-len", type=int, default=512)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--packing", action="store_true")
    ap.add_argument("--ckpt-dir")
    args = ap.parse_args()

    from hetu_tpu.core.mesh import MeshConfig
    from hetu_tpu.data import (DataCollatorForLanguageModel, DataLoader,
                               JsonDataset, TokenizedDataset)
    from hetu_tpu.engine import Trainer, TrainingConfig
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel
    from hetu_tpu.parallel import ParallelStrategy
    from hetu_tpu.utils.parallel_config import read_ds_parallel_config

    if args.config:
        from hetu_tpu.utils.yaml_config import load_experiment
        model, tc, strategy, _raw = load_experiment(args.config)
        if args.steps is not None:
            tc.total_steps = args.steps
        cfg = model.config
        if args.packing:
            tc.packing = True
        if args.ckpt_dir:
            tc.ckpt_dir = args.ckpt_dir
    elif args.ds_config:
        strategy, _ = read_ds_parallel_config(args.ds_config)
    else:
        strategy = ParallelStrategy(
            mesh=MeshConfig(dp=args.dp, tp=args.tp, pp=args.pp, cp=args.cp),
            sequence_parallel=args.sp)

    cfg = getattr(LlamaConfig, args.model)() if args.model != "tiny" \
        else LlamaConfig.tiny(vocab_size=50304)  # padded (divisible by tp)
    model = LlamaLMHeadModel(cfg, strategy)
    tc = TrainingConfig(
        global_batch_size=args.global_batch, micro_batch_size=args.micro_batch,
        seq_len=args.seq_len, lr=args.lr, total_steps=args.steps,
        packing=args.packing, ckpt_dir=args.ckpt_dir, log_every=10)

    if args.data:
        from transformers import AutoTokenizer
        tok = AutoTokenizer.from_pretrained(args.tokenizer)
        ds = JsonDataset(args.data, tok, max_seq_len=args.seq_len)
    else:
        ds = TokenizedDataset.synthetic(
            4096, vocab=cfg.vocab_size, min_len=args.seq_len // 4,
            max_len=args.seq_len, seed=0)
    coll = DataCollatorForLanguageModel(args.seq_len, packing=args.packing)
    dl = DataLoader(ds, tc.global_batch_size, coll)

    trainer = Trainer(model, tc, strategy).build()
    print(f"training {args.model} on {strategy.describe()} "
          f"({model.num_params()/1e6:.0f}M params)")

    def batches():
        epoch = 0
        while True:
            yield from dl.epoch(epoch)
            epoch += 1

    trainer.train(batches(), num_steps=args.steps)


if __name__ == "__main__":
    from hetu_tpu.utils.device import enable_compile_cache
    enable_compile_cache()
    main()
