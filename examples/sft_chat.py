"""Chat SFT demo: message templates -> tokenizer -> masked-label training
(reference: the lobra/SFT pipeline over python/hetu/data/messages).

A tiny LLaMA fine-tunes on a toy instruction dataset: samples flow through
InputOutputTemplate (user turns masked), the runtime-free in-tree
SentencePiece tokenizer, and the trainer — only assistant tokens (plus the
turn-closing eos) contribute loss.

Run:  JAX_PLATFORMS=cpu python examples/sft_chat.py
"""
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import numpy as np


def main():
    import jax

    from hetu_tpu.data import ChatFormat, InputOutputTemplate, build_sft_example
    from hetu_tpu.data.tokenizers.sp_model import (SentencePieceTokenizer,
                                                   write_model_proto)
    from hetu_tpu.engine import Trainer, TrainingConfig
    from hetu_tpu.models.llama import LlamaConfig, LlamaLMHeadModel

    # byte-fallback sp model built in-process (a real run loads
    # tokenizer.model via SentencePieceTokenizer(path))
    pieces = [("<unk>", 0.0, 2), ("<s>", 0.0, 3), ("</s>", 0.0, 3)]
    pieces += [(f"<0x{b:02X}>", 0.0, 6) for b in range(256)]
    tok = SentencePieceTokenizer(model_bytes=write_model_proto(
        pieces, 1, byte_fallback=True))

    dataset = [
        {"input": "name a color", "output": "blue"},
        {"input": "name a number", "output": "seven"},
        {"input": "name a fruit", "output": "plum"},
        {"input": "name a metal", "output": "iron"},
    ]
    template = InputOutputTemplate()
    fmt = ChatFormat()   # llama-chat-like [INST] framing
    seq = 64
    rows = [build_sft_example(s, template, tok.encode, chat_format=fmt,
                              bos_id=tok.bos_id, eos_id=tok.eos_id,
                              max_len=seq) for s in dataset]
    ids = np.zeros((len(rows), seq), np.int32)
    labels = np.full((len(rows), seq), -100, np.int32)
    for i, (r_ids, r_lab) in enumerate(rows):
        ids[i, :len(r_ids)] = r_ids
        labels[i, :len(r_lab)] = r_lab
    masked = float((labels == -100).sum()) / labels.size
    print(f"{len(rows)} samples; {masked:.0%} of label positions masked")

    cfg = LlamaConfig.tiny(remat=False, vocab_size=512)
    tc = TrainingConfig(global_batch_size=len(rows), micro_batch_size=2,
                        seq_len=seq, lr=3e-3, warmup_steps=2,
                        total_steps=60, log_every=1000)
    trainer = Trainer(LlamaLMHeadModel(cfg), tc).build(jax.random.key(0))
    batch = {"input_ids": ids, "labels": labels}
    for step in range(12):
        m = trainer.train_step(batch)
        if step % 3 == 0:
            print(f"step {step}: assistant-token loss "
                  f"{float(m['loss']):.4f}")


if __name__ == "__main__":
    main()
