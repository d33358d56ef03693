"""The LFM2-MoE family: gated short convolutions beside grouped-query
attention, over a dense or a sparse FFN.

Layer ``i``, pre-norm, RMSNorm with a learned weight, no bias anywhere:
``h = x + Op_i(norm x)``, ``out = h + FFN_i(norm h)``.

- ``layer_types[i] == "conv"``: ``[B | C | X] = u W_in``; ``z = B * X``;
  ``c_t = sum_j w[:, j] z_{t-(L-1)+j}`` (depthwise, causal, ``L =
  conv_L_cache`` taps, zeros before the sequence's start); ``Op(u) = (C *
  c) W_out``. All a sequence keeps of its past in such a layer is the last
  ``L - 1`` values of ``z``: a state of FIXED SIZE, whatever its length.
- ``"full_attention"``: grouped-query heads of ``hidden / heads``, RMSNorm
  over every query and key head BEFORE RoPE (all dims, half-rotation).
- FFN: SwiGLU for ``i < num_dense_layers``, else dropless sigmoid top-k
  routing (``moe/dropless.py``, every expert held, with this family's
  ``+ 1e-6`` and scaling factor).
- a final norm; the head is the embedding, tied.

The norms, RoPE, SwiGLU, the attention arithmetic (the paged step through a
block table with it), the sparse FFN and the decoder shell are
``models/blocks.py``'s; this file holds the config, the short convolution
and its state, the QK-normed attention and the pools.

SERVING. ``for_paged_decode`` gives the module the attention layers' KV
pools, addressed through the sequence's block table as every model's are,
and ONE MORE pool, ``conv_state_pool [conv layers, 1 + slots, L - 1,
hidden]``: row ``1 + s`` is decode slot ``s``'s state in every
convolution layer, row 0 what idle rows write. The engine hands each row
of a program its slot's state row as the table's last entry, through the
engine's per-slot seam (``paged_slot_state_for``,
``serving/engine.py``): state of fixed size a slot, written in place every
step. One function, :meth:`ShortConv.__call__`, serves the whole-prompt
prefill, a prefill chunk and a decode step: a sequence at length 0 starts
from zeros whatever its slot held, and the new state is taken at each
row's ``num_valid``, never at a bucket's end.
"""

import dataclasses
import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models import blocks
from deepspeed_tpu.models.decode_utils import embed_lookup


@dataclasses.dataclass(frozen=True)
class Lfm2MoeConfig(blocks.ServedConfig):
    vocab_size: int = 65536
    hidden_size: int = 2048
    num_hidden_layers: int = 24
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: Tuple[str, ...] = ()     # "conv" | "full_attention"
    num_dense_layers: int = 2
    intermediate_size: int = 7168
    moe_intermediate_size: int = 1792
    num_experts: int = 32
    num_experts_per_tok: int = 4
    routed_scaling_factor: float = 1.0
    # the top-k normalisation's ``+ eps`` in the denominator
    route_norm_eps: float = 1e-6
    conv_L_cache: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    max_position_embeddings: int = 128000
    # initialisation scales. Training moves all of them; a caller that
    # wants every operator visible to a comparison on random weights
    # (perfbench: a convolution's term is a product of THREE projections,
    # and vanishes at 0.02) sets them
    conv_in_std: float = 0.02
    conv_tap_std: float = 0.02
    conv_out_std: float = 0.02
    expert_bias_std: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # serving (for_paged_decode)
    decode: bool = False
    paged: bool = False
    paged_num_blocks: int = 0
    paged_block_size: int = 0
    paged_state_slots: int = 0
    paged_return_routed: bool = False

    def __post_init__(self):
        n = self.num_hidden_layers
        kinds = set(self.layer_types) - {"conv", "full_attention"}
        if len(self.layer_types) != n or kinds:
            raise ValueError(
                f"layer_types needs one of 'conv' / 'full_attention' a "
                f"layer ({n}), got {self.layer_types}")
        if self.hidden_size % self.num_attention_heads or (
                self.num_attention_heads % self.num_key_value_heads):
            raise ValueError(
                f"{self.num_attention_heads} heads over hidden "
                f"{self.hidden_size} and {self.num_key_value_heads} KV heads")

    # the contract's (blocks.ServedConfig): the slots' keyword, why
    # kv_dtype is refused, the layers that are sparse and their routing
    # (every expert held)
    slot_knob = "state_slots"
    unquantized = "convolution state has no quantized pool"

    def sparse(self, i: int) -> bool:
        return i >= self.num_dense_layers

    def sparse_ffn(self) -> dict:
        return dict(experts=self.num_experts, top_k=self.num_experts_per_tok,
                    width=self.moe_intermediate_size,
                    norm_eps=self.route_norm_eps,
                    scale=self.routed_scaling_factor,
                    bias_std=self.expert_bias_std, dtype=self.dtype,
                    param_dtype=self.param_dtype)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def layers_of(self, kind: str):
        """Indices of the layers of one kind, in order: a layer's place in
        its kind's pool is its place here."""
        return [i for i, k in enumerate(self.layer_types) if k == kind]

    def kv_bytes_per_token(self) -> dict:
        """Bytes of keys and values one token keeps (attention layers)."""
        item = jnp.dtype(self.dtype).itemsize
        return {"global": len(self.layers_of("full_attention"))
                * self.num_key_value_heads * 2 * self.head_dim * item}

    def state_bytes_per_slot(self) -> int:
        """Bytes a decode slot's convolution state takes, all layers."""
        return (len(self.layers_of("conv")) * (self.conv_L_cache - 1)
                * self.hidden_size * jnp.dtype(self.dtype).itemsize)

    def paged_slot_state_for(self, block_size: int):
        """What a decode slot keeps beside its block table (the engine's
        per-slot seam): one entry of the table, the slot's row of the
        state pool. None without convolution layers."""
        if not self.layers_of("conv"):
            return None
        return {"entries": 1, "knob": self.slot_knob,
                "what": "short-convolution layers keep a state of fixed "
                        "size a decode slot, written in place every step"}

    def kv_live_bytes(self, live) -> dict:
        """Bytes of per-sequence state a decode step reads, by kind, for
        busy rows of the lengths ``live``: the attention layers' keys and
        values of every token, the convolutions' state a busy slot."""
        return {"global": int(live.sum())
                * self.kv_bytes_per_token()["global"],
                "state": len(live) * self.state_bytes_per_slot()}

    @staticmethod
    def tiny(**kw):
        """The CPU tests' size: every mechanism, no published width. The
        convolutions' scales make them carry the residual stream, as they
        do at the published widths (where the embedding is 2% of it):
        at 0.02 a tied head would repeat its input whatever the state."""
        base = dict(vocab_size=128, hidden_size=64, num_hidden_layers=6,
                    num_attention_heads=8, num_key_value_heads=2,
                    layer_types=("conv", "conv", "full_attention", "conv",
                                 "conv", "full_attention"),
                    num_dense_layers=2, intermediate_size=128,
                    moe_intermediate_size=32, num_experts=32,
                    num_experts_per_tok=4, max_position_embeddings=256,
                    conv_in_std=0.125, conv_tap_std=0.33, conv_out_std=0.125,
                    expert_bias_std=0.01)
        base.update(kw)
        return Lfm2MoeConfig(**base)


def gated_inputs(u, w_in):
    """``[B | C | X] = u W_in``: the convolution's input gate, its output
    gate and what the two gate, each ``[B, T, C]``."""
    return jnp.split(jnp.dot(u, w_in), 3, axis=-1)


# (a module attribute of this file: controls replace it here)
short_conv = blocks.causal_conv


class ShortConv(nn.Module):
    """``(u, state, num_valid) -> (y, new state)``: the gated short
    convolution of a whole prompt (state zero), of a prefill chunk (state
    as stored) and of a decode step (``T = 1``) alike. Plain call: the
    whole sequence from zeros."""

    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, u, state=None, num_valid=None):
        cfg = self.config
        b, t, d = u.shape
        keep = cfg.conv_L_cache - 1
        w_in = self.param("in_proj", blocks.init(cfg.conv_in_std),
                          (d, 3 * d), cfg.param_dtype)
        taps = self.param("conv", blocks.init(cfg.conv_tap_std),
                          (d, cfg.conv_L_cache), cfg.param_dtype)
        gate_b, gate_c, x = gated_inputs(u, w_in.astype(cfg.dtype))
        z = gate_b * x
        if state is None:
            state = jnp.zeros((b, keep, d), z.dtype)
        if num_valid is None:
            num_valid = jnp.full((b,), t, jnp.int32)
        c, state = short_conv(z, taps, state, num_valid)
        y = (gate_c.astype(jnp.float32) * c).astype(cfg.dtype)
        return blocks.dense(cfg, "out_proj", d, cfg.conv_out_std)(y), state


def conv_state_in(pool, index, rows, lengths):
    """The state a paged call's rows start from, of convolution layer
    ``index``: what their slots hold (``rows [B]`` into the pool), and
    zeros for a sequence at length 0, whatever its slot's last tenant
    left."""
    held = pool[index, rows]
    return jnp.where((lengths == 0)[:, None, None], jnp.zeros_like(held),
                     held)


def _paged_conv(layer, u, paging, pools, index):
    """One convolution layer of a serving program: the slot's state in,
    the new state written in place."""
    from deepspeed_tpu.ops.attention import record_dispatch

    t = u.shape[1]
    form = ("prefill" if paging.get("prefill") else "decode" if t == 1
            else "chunk")
    record_dispatch(f"lfm2_conv_{form}")
    pool = pools["conv_state_pool"]
    rows = paging["block_tables"][:, -1]
    with jax.named_scope(f"lfm2._conv_{form}"):
        out, state = layer(
            u, conv_state_in(pool, index, rows, paging["lengths"]),
            paging["num_valid"])
        pool = pool.at[index, rows].set(state.astype(pool.dtype))
    return out, {**pools, "conv_state_pool": pool}


class Lfm2Attention(nn.Module):
    config: Lfm2MoeConfig

    @nn.compact
    def __call__(self, x, paging=None, pools=None, index=0, work=None):
        cfg = self.config
        b, t, _ = x.shape
        heads, kv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
        proj = functools.partial(blocks.dense, cfg)
        q = proj("q_proj", heads * dh)(x).reshape(b, t, heads, dh)
        k = proj("k_proj", kv * dh)(x).reshape(b, t, kv, dh)
        v = proj("v_proj", kv * dh)(x).reshape(b, t, kv, dh)
        # the norm of every query and key head, a weight a projection,
        # BEFORE the rotation
        q = blocks.RMSNorm(cfg.norm_eps, cfg.dtype, name="q_layernorm")(q)
        k = blocks.RMSNorm(cfg.norm_eps, cfg.dtype, name="k_layernorm")(k)
        pos = blocks.call_positions(cfg, paging, t)
        cos, sin = blocks.rope_frequencies(dh, pos, cfg.rope_theta)
        if cos.shape[0] == 1:
            cos, sin = cos[0], sin[0]
        q, k = blocks.apply_rope(q, cos, sin), blocks.apply_rope(k, cos, sin)
        if not cfg.serving:
            y = blocks.causal_gqa(q, k, v)
        else:
            # through the block table (its last entry is the state row)
            y, k_pool, v_pool = blocks.paged_gqa(
                q, k, v, pos, paging, paging["block_tables"][:, :-1],
                pools["global_key_pool"], pools["global_value_pool"], index,
                "lfm2_attn", work=work)
            pools = {**pools, "global_key_pool": k_pool,
                     "global_value_pool": v_pool}
        out = proj("o_proj", cfg.hidden_size)(y.reshape(b, t, heads * dh))
        return out, pools


class Lfm2MoeForCausalLM(blocks.PagedDecoder):
    """``blocks.PagedDecoder`` over the layer types; the head is the
    embedding."""

    config: Lfm2MoeConfig
    norms = ("operator_norm", "ffn_norm")
    eps_field = "norm_eps"
    tied = True
    # the dense FFN's weights are float32 whatever ``param_dtype`` says, as
    # they were when the block was the Llama family's
    dense_param_dtype = jnp.float32
    # the engine reads this leaf's layout to choose ``paging["lookup"]``
    lookup_table = "embed_tokens"

    def lookup(self, table, ids, paging):
        return embed_lookup(table, ids, (paging or {}).get("lookup", "rows"))

    def pool_shapes(self, num_blocks, block_size):
        """A key and a value pool of the attention layers (``[layers,
        blocks, block_size, kv_heads * head_dim]``, the engine's
        ``num_blocks``), and the convolution layers' state pool (row 0 for
        idle rows, then a row a slot)."""
        cfg = self.config
        shapes = {}
        attn, conv = (len(cfg.layers_of(k))
                      for k in ("full_attention", "conv"))
        if attn:
            row = (attn, num_blocks, block_size,
                   cfg.num_key_value_heads * cfg.head_dim)
            shapes["global_key_pool"] = shapes["global_value_pool"] = row
        if conv:
            shapes["conv_state_pool"] = (conv, 1 + cfg.paged_state_slots,
                                         cfg.conv_L_cache - 1,
                                         cfg.hidden_size)
        return shapes

    def step_work(self, paging):
        """The kernel's grid follows this step's lengths, the same for
        every attention layer."""
        from deepspeed_tpu.ops.hybrid_decode_attention import (
            hybrid_plan, hybrid_work_list)

        cfg = self.config
        tables = paging["block_tables"]
        lanes = cfg.num_key_value_heads * cfg.head_dim
        plan = hybrid_plan(cfg.paged_block_size, lanes, lanes,
                           tables.shape[-1] - 1)
        return hybrid_work_list(paging["lengths"], tables, plan)

    def mixer(self, i, u, paging, pools, work):
        cfg = self.config
        kind = cfg.layer_types[i]
        place = cfg.layers_of(kind).index(i)
        if kind != "conv":
            return Lfm2Attention(cfg, name=f"layers_{i}_attn")(
                u, paging, pools, place, work)
        layer = ShortConv(cfg, name=f"layers_{i}_conv")
        if cfg.serving:
            return _paged_conv(layer, u, paging, pools, place)
        return layer(u)[0], pools
