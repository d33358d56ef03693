"""CLI: tune GPT-2 125M at seq 1024 and write the best config found.

``python -m deepspeed_tpu.autotuning`` ≈ the reference's
``deepspeed --autotuning run`` entry (launcher/runner.py:351 routes into
autotuning). The best config lands in ``<results-dir>/best_config.json``,
a result the operator reads (it carries the model, seq and chip count it
was tuned for); nothing in the repo loads it.
"""

import argparse
import json

import jax

from deepspeed_tpu.autotuning import Autotuner, AutotuningConfig
from deepspeed_tpu.autotuning.cost_model import (ChipSpec,
                                                 probe_devices_subprocess)
from deepspeed_tpu.utils.device import peaks


def _pin_parent_to_cpu():
    # Pin the parent to CPU BEFORE any backend touch: a chip belongs to one
    # process until it exits, and a parent holding it would make every
    # trial subprocess fail. `jax_platforms` set here, before the first
    # backend starts, is all it takes. Param counting (jax.eval_shape) is
    # host-side and doesn't need the chip; chip identity is probed in a
    # throwaway subprocess that exits before the first trial starts. (The
    # --live path does the opposite on purpose: its measurements run
    # in-process on whatever backend the operator launched with.)
    jax.config.update("jax_platforms", "cpu")

_PRESETS = {
    "gpt2-125m": {"n_layer": 12, "n_embd": 768, "n_head": 12,
                  "vocab_size": 50257, "n_positions": 1024,
                  "scan_layers": True, "dtype": "bfloat16"},
    "gpt2-tiny": {"n_layer": 2, "n_embd": 64, "n_head": 4,
                  "vocab_size": 256, "n_positions": 64,
                  "dtype": "float32"},
}


def main(argv=None):
    p = argparse.ArgumentParser(prog="python -m deepspeed_tpu.autotuning")
    p.add_argument("--model", default="gpt2-125m", choices=sorted(_PRESETS))
    p.add_argument("--seq-len", type=int, default=None,
                   help="default: the model's n_positions")
    p.add_argument("--micro-batches", default=None,
                   help="comma list, e.g. 8,16,24 (default: derived)")
    p.add_argument("--zero-stages", default=None, help="comma list")
    p.add_argument("--remat-policies", default="none,dots,full")
    p.add_argument("--tuner", default="model_based",
                   choices=["model_based", "gridsearch", "random"])
    p.add_argument("--max-trials", type=int, default=12)
    p.add_argument("--steps", type=int, default=5)
    p.add_argument("--results-dir", default="autotuning_results")
    p.add_argument("--hbm-gib", type=float, default=None,
                   help="override HBM capacity for space pruning "
                        "(default: probed from the chip)")
    p.add_argument("--in-process", action="store_true",
                   help="no subprocess isolation (debug only)")
    p.add_argument("--live", action="store_true",
                   help="measured live-tunable search instead of the "
                        "offline launch-config search: walk the axis "
                        "registry (Pallas tiles, reduction bucket bytes, "
                        "collective tier, serving prefill shape) on the "
                        "package's own series (autotuning/series.py) and write "
                        "<results-dir>/tuned.json (consumed by the "
                        "`tuning` config block)")
    p.add_argument("--axes", default=None,
                   help="--live only: comma list of axis names "
                        "(default: the full registry)")
    args = p.parse_args(argv)

    if args.live:
        from deepspeed_tpu.autotuning.measure import LiveTuner

        names = args.axes.split(",") if args.axes else None
        artifact = LiveTuner(results_dir=args.results_dir).tune(
            axis_names=names)
        print(json.dumps({
            "results_dir": args.results_dir,
            "fingerprint_hash": artifact["fingerprint_hash"],
            "chosen": {n: a["value"] for n, a in artifact["axes"].items()
                       if a["value"] is not None},
        }))
        return

    _pin_parent_to_cpu()
    model_cfg = _PRESETS[args.model]
    seq = args.seq_len or model_cfg.get("n_positions", 1024)
    platform, kind, n_dev, hbm_bytes = probe_devices_subprocess()
    chip = ChipSpec.from_kind(kind)  # unknown kind: DeviceError
    hbm_gib = (args.hbm_gib if args.hbm_gib is not None
               else (hbm_bytes or peaks(kind).hbm_bytes) / (1 << 30))
    atc = AutotuningConfig(
        enabled=True,
        tuner_type=args.tuner,
        max_trials=args.max_trials,
        trial_steps=args.steps,
        micro_batch_sizes=(
            [int(x) for x in args.micro_batches.split(",")]
            if args.micro_batches else None),
        zero_stages=([int(x) for x in args.zero_stages.split(",")]
                     if args.zero_stages else None),
        remat_policies=args.remat_policies.split(","),
        results_dir=args.results_dir,
        hbm_gib=hbm_gib,
        in_process=args.in_process)
    base = {
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 6e-4, "weight_decay": 0.1}},
        "gradient_clipping": 1.0,
        "bf16": {"enabled": model_cfg.get("dtype") == "bfloat16"},
        "steps_per_print": 10_000,
    }
    best = Autotuner(model_spec={"preset": "gpt2", "config": model_cfg},
                     base_ds_config=base, config=atc, seq_len=seq,
                     chip=chip, dp=n_dev).tune()
    if best is None:
        raise SystemExit("autotuning produced no feasible config")
    print(json.dumps(best))


if __name__ == "__main__":
    main()
