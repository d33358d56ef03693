"""Generate text from a checkpoint saved by ``train_gpt2.py``.

    python examples/serve_gpt2.py --checkpoint /tmp/ds_tpu_example \
        --prompt "A TPU-native framework " --tokens 120
"""

import argparse
import os
import sys

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import deepspeed_tpu
from deepspeed_tpu.inference.engine import load_module_params
from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel


def main():
    p = argparse.ArgumentParser(description="byte-level GPT-2 serving")
    p.add_argument("--checkpoint", default="/tmp/ds_tpu_example")
    p.add_argument("--tag", default="example")
    p.add_argument("--prompt", default="A TPU-native framework ")
    p.add_argument("--tokens", type=int, default=120)
    p.add_argument("--seq", type=int, default=128)
    p.add_argument("--temperature", type=float, default=0.8)
    args = p.parse_args()

    model = GPT2LMHeadModel(GPT2Config(
        vocab_size=256, n_positions=args.seq, n_embd=128, n_layer=4,
        n_head=4))
    params = load_module_params(args.checkpoint, tag=args.tag)
    engine = deepspeed_tpu.init_inference(model, params=params, dtype="fp32",
                                          max_out_tokens=args.seq)

    ids = np.frombuffer(args.prompt.encode(), np.uint8)[None].astype(np.int32)
    if ids.shape[1] >= args.seq:  # keep the window's most recent context
        print(f"[prompt truncated to its last {args.seq - 1} bytes]")
        ids = ids[:, -(args.seq - 1):]
    tokens = max(1, min(args.tokens, args.seq - ids.shape[1]))  # window cap
    if tokens < args.tokens:
        print(f"[prompt {ids.shape[1]} bytes + {args.tokens} tokens exceeds "
              f"the {args.seq}-position window; generating {tokens}]")
    out = engine.generate(ids, max_new_tokens=tokens, do_sample=True,
                          temperature=args.temperature, top_k=40)
    text = bytes(np.asarray(out)[0].tolist()).decode("utf-8", errors="replace")
    print(text)


if __name__ == "__main__":
    main()
