"""Tuning-space enumeration and memory-model pruning.

The reference prunes its experiment space with a measured model-info
profile run (params + activation memory per micro-batch,
deepspeed/autotuning/autotuner.py:426 ``model_info_profile_run``) before
launching experiments. Here the same job is done with a closed-form HBM
model: JAX can report parameter counts without touching the device
(``jax.eval_shape``), and transformer activation footprints are predictable
enough per remat policy to rank candidates. Estimates are deliberately
conservative (see ``memory_headroom``); a candidate that still OOMs is
caught by its isolated trial process and recorded as infeasible.
"""

import dataclasses
import itertools
from typing import Dict, List, Optional


@dataclasses.dataclass(frozen=True)
class ModelProfile:
    """Static facts about the model being tuned."""

    n_params: int
    n_layer: int
    n_embd: int
    vocab_size: int
    seq_len: int
    act_bytes: int = 2  # bf16 activations

    @property
    def flops_per_token(self) -> int:
        # 6N matmul FLOPs (fwd+bwd) + causal attention (PaLM appendix B,
        # halved for causality).
        return 6 * self.n_params + 6 * self.n_layer * self.seq_len * self.n_embd


@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point in the tuning space.

    ``micro_batch`` is per-chip; ``remat_policy`` maps onto the model's
    activation-checkpointing config ("none" disables remat, "dots"/"full"
    select the jax.checkpoint policy); ``fused_step`` compiles
    fwd+bwd+optimizer into one program (gas=1 only).
    """

    micro_batch: int
    zero_stage: int
    remat_policy: str
    fused_step: bool = True

    def ds_config_overrides(self) -> Dict:
        return {
            "train_micro_batch_size_per_gpu": self.micro_batch,
            "zero_optimization": {"stage": self.zero_stage},
            "fused_step": self.fused_step,
            "activation_checkpointing": {
                "partition_activations": False,
                "enabled": self.remat_policy != "none",
                "policy": self.remat_policy,
            },
        }

    def name(self) -> str:
        return (f"mb{self.micro_batch}_z{self.zero_stage}"
                f"_remat-{self.remat_policy}"
                + ("_fused" if self.fused_step else ""))


# Saved-activation sizes per token per layer, in units of n_embd elements.
# "none": every intermediate alive for backward (qkv, attention out, 4C mlp
# hidden, gelu, projections, LNs, residuals). "dots": matmul outputs + flash
# residuals only (elementwise chains recomputed). "full": just the block
# boundary. Calibrated against xprof memory profiles of GPT-2 125M;
# deliberately round numbers — this ranks candidates, it does not bill them.
_ACT_UNITS = {"none": 30.0, "dots": 12.0, "full": 2.0}


def estimate_hbm_bytes(profile: ModelProfile, cand: Candidate,
                       dp: int = 1) -> int:
    """Closed-form peak-HBM estimate for one candidate.

    ZeRO factors follow the stage semantics (SURVEY §2.2): stage>=1 shards
    optimizer state (fp32 masters + Adam moments) over dp, stage>=2 shards
    gradients, stage>=3 shards the bf16 compute params.
    """
    n = profile.n_params
    opt_div = dp if cand.zero_stage >= 1 else 1
    grad_div = dp if cand.zero_stage >= 2 else 1
    param_div = dp if cand.zero_stage >= 3 else 1

    params = 2 * n // param_div            # bf16 compute copy
    masters = 4 * n // opt_div             # fp32 master weights
    moments = 8 * n // opt_div             # Adam m+v fp32
    grads = 4 * n // grad_div              # fp32 grads / grad-acc buffer
    if cand.fused_step:
        grads //= 2                        # consumed in-program, bf16-sized peak

    tokens = cand.micro_batch * profile.seq_len
    act_units = _ACT_UNITS.get(cand.remat_policy, _ACT_UNITS["dots"])
    acts = int(tokens * profile.n_layer * act_units * profile.n_embd
               * profile.act_bytes)
    # LM-head logits: fp32 [B, T, V] when the dense head is in play — the
    # single biggest activation for small models with big vocabs.
    logits = 4 * tokens * profile.vocab_size

    return params + masters + moments + grads + acts + logits


def device_hbm_bytes(override_gib: Optional[float] = None) -> int:
    """HBM budget: an explicit ``override_gib`` wins; otherwise the live
    device's reported limit; otherwise 16 GiB."""
    if override_gib is not None:
        return int(override_gib * (1 << 30))
    try:
        import jax

        stats = jax.devices()[0].memory_stats()
        if stats and "bytes_limit" in stats:
            return int(stats["bytes_limit"])
    except Exception:
        pass
    return int(16.0 * (1 << 30))


def build_space(profile: ModelProfile,
                micro_batch_sizes: Optional[List[int]],
                zero_stages: Optional[List[int]],
                remat_policies: List[str],
                hbm_bytes: int,
                headroom: float = 0.9,
                dp: int = 1,
                fused_steps: Optional[List[bool]] = None) -> List[Candidate]:
    """Enumerate candidates and drop those the memory model rules out.

    Micro-batches default to powers of two from 1 up to the largest size any
    remat policy can fit (reference sweeps mbs the same way,
    autotuner.py:657 ``get_min_max_micro_batch_size``). ZeRO stages beyond 0
    only enter the space when dp > 1 (sharding over one device is a no-op).
    """
    if zero_stages is None:
        zero_stages = [0, 1, 2, 3] if dp > 1 else [0]
    if fused_steps is None:
        fused_steps = [True]
    if micro_batch_sizes is None:
        micro_batch_sizes, mb = [], 1
        while mb <= 4096:
            fits = any(
                estimate_hbm_bytes(
                    profile, Candidate(mb, max(zero_stages), pol), dp)
                <= headroom * hbm_bytes
                for pol in remat_policies)
            if not fits:
                break
            micro_batch_sizes.append(mb)
            mb *= 2

    budget = headroom * hbm_bytes
    space = []
    for mb, stage, pol, fused in itertools.product(
            micro_batch_sizes, zero_stages, remat_policies, fused_steps):
        cand = Candidate(micro_batch=mb, zero_stage=stage, remat_policy=pol,
                         fused_step=fused)
        if estimate_hbm_bytes(profile, cand, dp) <= budget:
            space.append(cand)
    return space
