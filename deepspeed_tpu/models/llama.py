"""Llama model family — TPU-native flax implementation.

Covers the BASELINE.md tracked config "Llama-2 7B ZeRO-3 on v5p-64" and
the reference's HF-architecture support surface
(``model_implementations/``, ``module_inject/replace_policy.py`` LLaMA-style
archs): RMSNorm, rotary position embeddings, SwiGLU MLP, grouped-query
attention, no biases. Mirrors models/gpt2.py's engine integration — scanned
layers (one compiled block, per-layer ZeRO-3 gathers), config-driven remat,
KV-cache decode mode, and the ``*ForTraining`` wrapper contract.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.blocks import (RMSNorm, SwiGLU, apply_rope,
                                         init as _init, rope_frequencies)
from deepspeed_tpu.models.gpt2 import lm_head_loss, shift_labels
from deepspeed_tpu.models.remat_utils import offload_policy, saved_block_input
from deepspeed_tpu.ops.attention import attention


@dataclasses.dataclass(frozen=True)
class LlamaConfig:
    vocab_size: int = 32000
    max_position_embeddings: int = 4096
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: Optional[int] = None  # None = MHA; < heads = GQA
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    dtype: Any = jnp.bfloat16
    scan_layers: bool = True
    remat: bool = False
    remat_policy: str = "full"
    # host-offloaded / model-axis-partitioned saved activations — see
    # models/gpt2.py GPT2Config for the reference mapping (ref
    # checkpointing.py:485 / :372)
    cpu_checkpointing: bool = False
    partition_activations: bool = False
    use_flash: Optional[bool] = None
    decode: bool = False
    # padded decode: LEFT-padded prompts (attention_mask at prefill);
    # decode steps mask each row's padded cache prefix and shift positions.
    # Static so unpadded serving keeps the Pallas decode kernel
    padded: bool = False

    @property
    def kv_heads(self) -> int:
        return self.num_key_value_heads or self.num_attention_heads

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def for_decode(self, padded: bool = False):
        return dataclasses.replace(self, decode=True, padded=padded)

    @staticmethod
    def llama2_7b(**kw):
        return LlamaConfig(**kw)

    @staticmethod
    def tiny(**kw):
        kw.setdefault("vocab_size", 256)
        kw.setdefault("max_position_embeddings", 64)
        kw.setdefault("hidden_size", 64)
        kw.setdefault("intermediate_size", 128)
        kw.setdefault("num_hidden_layers", 2)
        kw.setdefault("num_attention_heads", 4)
        return LlamaConfig(**kw)


class LlamaAttention(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, deterministic=True, attention_mask=None):
        from deepspeed_tpu.models.decode_utils import (cache_attn_mask,
                                                       decode_positions,
                                                       pad_lengths,
                                                       row_positions)

        cfg = self.config
        B, T, C = x.shape
        H, KV, D = cfg.num_attention_heads, cfg.kv_heads, cfg.head_dim
        q = nn.Dense(H * D, use_bias=False, dtype=cfg.dtype,
                     kernel_init=_init(), name="q_proj")(x)
        k = nn.Dense(KV * D, use_bias=False, dtype=cfg.dtype,
                     kernel_init=_init(), name="k_proj")(x)
        v = nn.Dense(KV * D, use_bias=False, dtype=cfg.dtype,
                     kernel_init=_init(), name="v_proj")(x)
        q = q.reshape(B, T, H, D)
        k = k.reshape(B, T, KV, D)
        v = v.reshape(B, T, KV, D)

        if cfg.decode:
            is_prefill = not self.has_variable("cache", "cached_key")
            S = cfg.max_position_embeddings
            ck = self.variable("cache", "cached_key", jnp.zeros,
                               (B, S, KV, D), cfg.dtype)
            cv = self.variable("cache", "cached_value", jnp.zeros,
                               (B, S, KV, D), cfg.dtype)
            cidx = self.variable("cache", "cache_index",
                                 lambda: jnp.zeros((), jnp.int32))
            idx = cidx.value
            pad = None
            if cfg.padded:
                pl = self.variable("cache", "pad_len",
                                   lambda: jnp.zeros((B,), jnp.int32))
                if is_prefill and attention_mask is not None:
                    pl.value = pad_lengths(attention_mask, T)
                pad = pl.value
            if cfg.padded and is_prefill and attention_mask is not None:
                pos = row_positions(attention_mask)  # [B, T]
            elif cfg.padded and not is_prefill:
                pos = decode_positions(idx, T, pad)
            else:
                pos = idx + jnp.arange(T)
            cos, sin = rope_frequencies(D, pos, cfg.rope_theta)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)
            ck.value = jax.lax.dynamic_update_slice(ck.value, k,
                                                    (0, idx, 0, 0))
            cv.value = jax.lax.dynamic_update_slice(cv.value, v,
                                                    (0, idx, 0, 0))
            cidx.value = idx + T
            if not is_prefill:
                kc = ck.value
                vc = cv.value
                rep = H // KV
                kc = jnp.repeat(kc, rep, axis=2) if rep > 1 else kc
                vc = jnp.repeat(vc, rep, axis=2) if rep > 1 else vc
                from deepspeed_tpu.ops.attention import use_decode_kernel

                if use_decode_kernel() and not cfg.padded:
                    from deepspeed_tpu.ops.decode_attention import (
                        decode_attention_tp)

                    # heads partitioned over the tp axis (plain kernel
                    # when tp is inactive)
                    y = decode_attention_tp(q, kc, vc,
                                            idx).transpose(0, 2, 1, 3)
                else:
                    mask = cache_attn_mask(S, idx, T,
                                            pad if cfg.padded else None)
                    y = attention(q.transpose(0, 2, 1, 3),
                                  kc.transpose(0, 2, 1, 3),
                                  vc.transpose(0, 2, 1, 3),
                                  mask=mask, causal=False,
                                  use_flash=False)
                y = y.transpose(0, 2, 1, 3).reshape(B, T, H * D)
                return nn.Dense(C, use_bias=False, dtype=cfg.dtype,
                                kernel_init=_init(), name="o_proj")(y)
        else:
            pos = (row_positions(attention_mask)
                   if attention_mask is not None else jnp.arange(T))
            cos, sin = rope_frequencies(D, pos, cfg.rope_theta)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)

        # training forward / decode prefill: causal attention over own keys
        rep = H // KV
        if rep > 1:  # GQA: expand kv heads to match q heads
            k = jnp.repeat(k, rep, axis=2)
            v = jnp.repeat(v, rep, axis=2)
        key_valid = (attention_mask[:, None, None, :].astype(bool)
                     if attention_mask is not None else None)
        y = attention(q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
                      v.transpose(0, 2, 1, 3), causal=True, mask=key_valid,
                      use_flash=cfg.use_flash
                      if attention_mask is None else False)
        y = y.transpose(0, 2, 1, 3).reshape(B, T, H * D)
        return nn.Dense(C, use_bias=False, dtype=cfg.dtype,
                        kernel_init=_init(), name="o_proj")(y)


class LlamaBlock(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, deterministic=True, attention_mask=None):
        cfg = self.config
        x = x + LlamaAttention(cfg, name="self_attn")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="input_layernorm")(x),
            deterministic=deterministic, attention_mask=attention_mask)
        x = x + SwiGLU(cfg.intermediate_size, cfg.hidden_size, cfg.dtype,
                       name="mlp")(
            RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                    name="post_attention_layernorm")(x))
        return x


def _remat_block(cfg):
    """Same policy surface as models/gpt2.py:_remat_block."""
    if not cfg.remat:
        return LlamaBlock
    if cfg.cpu_checkpointing:
        # the outer stack-level checkpoint in LlamaModel owns recompute +
        # host offload (models/remat_utils.py offload_policy rationale)
        return LlamaBlock
    policy = None
    if cfg.remat_policy == "dots":
        policy = jax.checkpoint_policies.save_from_both_policies(
            jax.checkpoint_policies.checkpoint_dots,
            jax.checkpoint_policies.save_only_these_names(
                "flash_q", "flash_k", "flash_v", "flash_o", "flash_lse"))
    return nn.remat(LlamaBlock, prevent_cse=False, policy=policy,
                    static_argnums=(2,))


class _ScanBody(nn.Module):
    config: LlamaConfig

    @nn.compact
    def __call__(self, x, deterministic, attention_mask):
        if self.config.remat:
            x = saved_block_input(x, self.config)
        x = _remat_block(self.config)(self.config, name="block")(
            x, deterministic, attention_mask)
        return x, None


class LlamaModel(nn.Module):
    """Decoder stack → final RMSNorm → (tied or separate) LM head."""

    config: LlamaConfig

    @nn.compact
    def __call__(self, input_ids, deterministic=True, return_hidden=False,
                 attention_mask=None):
        cfg = self.config
        embed = self.param("embed_tokens", _init(),
                           (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        x = embed[input_ids].astype(cfg.dtype)
        offload = cfg.remat and cfg.cpu_checkpointing
        if cfg.scan_layers:
            Scanned = nn.scan(
                _ScanBody,
                variable_axes={"params": 0, "cache": 0},
                split_rngs={"params": True, "dropout": True},
                in_axes=(nn.broadcast, nn.broadcast),
                length=cfg.num_hidden_layers,
                metadata_params={nn.meta.PARTITION_NAME: "layers"})
            if offload:
                # one stack-level checkpoint host-offloading the per-layer
                # "block_in" residuals (models/remat_utils.py offload_policy);
                # deterministic (arg 2 counting self) is static → positional
                Scanned = nn.remat(Scanned, prevent_cse=False,
                                   policy=offload_policy(cfg),
                                   static_argnums=(2,))
            x, _ = Scanned(cfg, name="layers")(x, deterministic,
                                               attention_mask)
        else:
            block_cls = _remat_block(cfg)

            def _stack(mdl, h, det, mask):
                for i in range(cfg.num_hidden_layers):
                    if cfg.remat:
                        h = saved_block_input(h, cfg)
                    h = block_cls(cfg, name=f"layers_{i}", parent=mdl)(
                        h, det, mask)
                return h

            if offload:
                # lifted remat on a (module, ...) function keeps the
                # layers_{i} param paths unchanged while the one outer
                # checkpoint host-offloads every block's input residual
                x = nn.remat(_stack, prevent_cse=False,
                             policy=offload_policy(cfg),
                             static_argnums=(2,))(self, x, deterministic,
                                                  attention_mask)
            else:
                x = _stack(self, x, deterministic, attention_mask)
        x = RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="norm")(x)
        if cfg.tie_word_embeddings:
            head = embed
        else:
            head = self.param("lm_head", _init(),
                              (cfg.vocab_size, cfg.hidden_size), jnp.float32)
        if return_hidden:
            return x, head
        return jnp.einsum("btc,vc->btv", x, head.astype(cfg.dtype),
                          preferred_element_type=jnp.float32)


def llama_loss_fn(model: LlamaModel):
    """Engine-facing loss (same contract/dense-vs-chunked-head logic as
    models/gpt2.py:gpt2_loss_fn)."""

    def loss_fn(params, batch, rngs=None):
        if isinstance(batch, dict):
            input_ids, labels = batch["input_ids"], batch.get("labels")
        else:
            input_ids, labels = batch
        if labels is None:
            labels = input_ids
        hidden, head = model.apply({"params": params}, input_ids,
                                   deterministic=rngs is None, rngs=rngs,
                                   return_hidden=True)
        return lm_head_loss(
            hidden, head, shift_labels(labels),
            dense_budget=3_500_000_000 if model.config.remat
            else 1_000_000_000)

    return loss_fn


class LlamaForTraining:
    """Engine-ready wrapper (same contract as GPT2ForTraining)."""

    def __init__(self, config: LlamaConfig):
        self.config = config
        self.model = LlamaModel(config)
        self.loss_fn = llama_loss_fn(self.model)

    @staticmethod
    def _input_ids(batch):
        if isinstance(batch, dict):
            return batch["input_ids"]
        if isinstance(batch, (tuple, list)):
            return batch[0]
        return batch

    def init(self, rng, batch):
        return self.model.init(rng, self._input_ids(batch))

    def apply(self, variables, batch, rngs=None):
        return self.model.apply(variables, self._input_ids(batch), rngs=rngs)

    def with_activation_checkpointing(self, enabled: bool,
                                      policy: str = "full",
                                      cpu_checkpointing: bool = False,
                                      partition_activations: bool = False):
        if policy == "none":
            enabled, policy = False, "full"
        return LlamaForTraining(dataclasses.replace(
            self.config, remat=enabled, remat_policy=policy,
            cpu_checkpointing=cpu_checkpointing,
            partition_activations=partition_activations))
