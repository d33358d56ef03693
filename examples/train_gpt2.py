"""Train a byte-level GPT-2 on a text file — the framework's "hello world".

The shape of a reference DeepSpeed training script (argparse +
``add_config_arguments`` + ``initialize`` + forward/backward/step), on the
TPU-native engine. Runs anywhere jax runs; on CPU finishes in ~a minute:

    python examples/train_gpt2.py --steps 100
    python examples/train_gpt2.py --deepspeed_config examples/ds_config.json

Then generate from the saved checkpoint:

    python examples/serve_gpt2.py --checkpoint /tmp/ds_tpu_example
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2ForTraining

DEFAULT_CORPUS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                              "..", "tests", "model", "corpus.txt")


def get_args():
    p = argparse.ArgumentParser(description="byte-level GPT-2 training")
    p.add_argument("--corpus", default=DEFAULT_CORPUS)
    p.add_argument("--steps", type=int, default=100)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--save_dir", default="/tmp/ds_tpu_example")
    p.add_argument("--local_rank", type=int, default=-1)  # launcher-injected
    deepspeed_tpu.add_config_arguments(p)   # --deepspeed / --deepspeed_config
    return p.parse_args()


def batches(corpus_bytes, batch, seq, rng):
    """Random contiguous byte windows, next-byte targets built by the
    model's shifted loss (labels == input_ids)."""
    while True:
        starts = rng.integers(0, len(corpus_bytes) - seq - 1, size=batch)
        yield np.stack([corpus_bytes[s:s + seq] for s in starts])


def main():
    args = get_args()
    config = args.deepspeed_config or {
        "train_batch_size": 8,
        "optimizer": {"type": "AdamW", "params": {"lr": 3e-3}},
        "scheduler": {"type": "WarmupLR", "params": {"warmup_num_steps": 20}},
        "gradient_clipping": 1.0,
        "steps_per_print": 20,
    }

    model = GPT2ForTraining(GPT2Config(
        vocab_size=256,          # bytes
        n_positions=args.seq, n_embd=128, n_layer=4, n_head=4))
    engine, _, _, _ = deepspeed_tpu.initialize(args=args, model=model,
                                               config=config)

    corpus = np.frombuffer(open(args.corpus, "rb").read(), np.uint8)
    corpus = corpus.astype(np.int32)
    rng = np.random.default_rng(0)
    # one engine() call consumes ONE micro-batch; the engine applies the
    # optimizer every gradient_accumulation_steps calls (the reference's
    # micro-step contract), so --steps counts micro-steps
    stream = batches(corpus, engine.train_micro_batch_size_per_gpu(),
                     args.seq, rng)

    first = None
    for step in range(args.steps):
        ids = next(stream)
        loss = engine({"input_ids": ids})
        engine.backward(loss)
        engine.step()
        if first is None:
            first = float(loss)
    print(f"loss: {first:.3f} -> {float(loss):.3f} over {args.steps} "
          "micro-steps")

    engine.save_checkpoint(args.save_dir, tag="example")
    print(f"checkpoint saved to {args.save_dir} (tag 'example')")


if __name__ == "__main__":
    main()
