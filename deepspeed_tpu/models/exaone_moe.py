"""The EXAONE-MoE family (``model_type: exaone_moe``): QK-normed window
layers beside position-free global layers, over a dense or a sparse FFN
with one shared expert.

Layer ``l``, pre-norm, RMSNorm with a learned weight, no bias anywhere:
``x += Attn_l(norm x)``, ``x += FFN_l(norm x)``; a final norm and an untied
head.

- attention: grouped-query heads of ``head_dim`` (keys and values alike),
  RMSNorm over every query and key head (one weight of ``head_dim`` a
  projection) BEFORE any rotation. ``layer_types[l]`` ``sliding_attention``:
  RoPE over the whole head (half-rotation) and the last ``sliding_window``
  keys, the query's own among them; ``full_attention``: NO rotation (the
  layer reads no position), causal over the whole context. One head shape
  in both kinds, no sink, no value scale;
- FFN: ``mlp_layer_types[l]`` ``dense`` = SwiGLU; ``sparse`` = dropless
  sigmoid top-k routing over ``num_experts`` (``moe/dropless.py``) of which
  this rank holds ``num_experts / ep_size``, the chosen scores renormalised
  (``+ 1e-20``) and scaled by ``routed_scaling_factor``, plus the shared
  experts' SwiGLU, ungated, which every share computes alike.

The norms, RoPE, SwiGLU, the attention arithmetic (the paged step through a
block table and the window kind's ring with it), the sparse FFN and the
decoder shell are ``models/blocks.py``'s; this file holds the config, the
attention of the two kinds and their pools. Layers are unrolled.

SERVING. ``for_paged_decode`` gives the module two pairs of KV pools of ONE
row shape (``kv_heads * head_dim`` lanes for keys, the same for values):
``global_*_pool`` is addressed through the sequence's block table and keeps
the context; ``window_*_pool`` holds one RING of ``ring_blocks`` blocks a
decode slot, whatever the context (``blocks.ring_gqa``). The engine hands
one table a row: the sequence's blocks, then the slot's ring
(``serving/engine.py``, the per-slot seam).
"""

import dataclasses
import functools
from typing import Any, Tuple

import flax.linen as nn
import jax.numpy as jnp

from deepspeed_tpu.models import blocks
from deepspeed_tpu.moe import dropless

WINDOW, GLOBAL = "sliding_attention", "full_attention"
# keys a tile of a prefill chunk's global attention (``blocks.cached_gqa``):
# 64 heads x 512 queries x 1,024 keys of float32 scores are 134 MB
CHUNK_KEY_TILE = 1024


@dataclasses.dataclass(frozen=True)
class ExaoneMoeConfig(blocks.ServedConfig):
    vocab_size: int = 153600
    hidden_size: int = 6144
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 8
    head_dim: int = 128
    layer_types: Tuple[str, ...] = ()       # WINDOW | GLOBAL
    mlp_layer_types: Tuple[str, ...] = ()   # "dense" | "sparse"
    intermediate_size: int = 18432
    moe_intermediate_size: int = 2048
    num_experts: int = 128                  # the router's width: ALL experts
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    # the top-k normalisation's ``+ eps`` in the denominator
    route_norm_eps: float = 1e-20
    # the experts held here: rank ep_rank of ep_size equal contiguous shares
    ep_rank: int = 0
    ep_size: int = 1
    sliding_window: int = 128
    rope_theta: float = 1e6
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    # the selection bias is a balancing term that training moves from zero;
    # a caller that wants the path exercised by random weights draws it
    selection_bias_std: float = 0.0
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # serving (for_paged_decode)
    decode: bool = False
    paged: bool = False
    paged_num_blocks: int = 0
    paged_block_size: int = 0
    paged_ring_slots: int = 0
    # the serving programs also hand back each token's chosen experts
    paged_return_routed: bool = False

    def __post_init__(self):
        n = self.num_hidden_layers
        odd = (set(self.layer_types) - {WINDOW, GLOBAL}
               or set(self.mlp_layer_types) - {"dense", "sparse"})
        if (len(self.layer_types) != n or len(self.mlp_layer_types) != n
                or odd):
            raise ValueError(
                f"layer_types ({WINDOW!r} / {GLOBAL!r}) and mlp_layer_types "
                f"('dense' / 'sparse') need one entry a layer ({n}), got "
                f"{self.layer_types} and {self.mlp_layer_types}")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} heads over "
                f"{self.num_key_value_heads} KV heads")
        dropless.held_range(self.num_experts, self.ep_rank, self.ep_size)

    # the contract's (blocks.ServedConfig): the slots' keyword, why
    # kv_dtype is refused, the layers that are sparse and their routing
    slot_knob = "ring_slots"
    unquantized = "two kinds of KV layer have no quantized pool yet"

    def sparse(self, i: int) -> bool:
        return self.mlp_layer_types[i] == "sparse"

    def sparse_ffn(self) -> dict:
        return dict(experts=self.num_experts, top_k=self.num_experts_per_tok,
                    width=self.moe_intermediate_size, scoring="sigmoid",
                    renormalize=True, norm_eps=self.route_norm_eps,
                    scale=self.routed_scaling_factor,
                    bias_std=self.selection_bias_std,
                    shared_width=(self.num_shared_experts
                                  * self.moe_intermediate_size),
                    ep_rank=self.ep_rank, ep_size=self.ep_size,
                    dtype=self.dtype, param_dtype=self.param_dtype)

    def layers_of(self, window: bool):
        """Indices of the layers of one kind, in order: a layer's place in
        its kind's pool is its place here."""
        return [i for i, kind in enumerate(self.layer_types)
                if (kind == WINDOW) == window]

    def paged_ring_blocks_for(self, block_size: int) -> int:
        """Blocks in a slot's ring (``blocks.ring_blocks_for``); 0 without
        window layers."""
        if not self.layers_of(True):
            return 0
        return blocks.ring_blocks_for(self.sliding_window, block_size)

    def paged_slot_state_for(self, block_size: int):
        """What a decode slot keeps beside its block table (the engine's
        per-slot seam, ``serving/engine.py``): its ring, ``entries``
        blocks of the window pool. None without window layers."""
        return blocks.ring_slot_state(
            self.paged_ring_blocks_for(block_size), self.slot_knob)

    def kv_bytes_per_token(self) -> dict:
        """Bytes of keys and values one token keeps, by kind of layer."""
        row = (self.num_key_value_heads * 2 * self.head_dim
               * jnp.dtype(self.dtype).itemsize)
        return {kind: len(self.layers_of(window)) * row
                for kind, window in (("global", False), ("window", True))}

    def kv_live_bytes(self, live) -> dict:
        """Bytes of keys and values a decode step reads, by kind of layer,
        for busy rows of the lengths ``live``: a global layer every token
        of a sequence, a window layer what the slot's ring holds."""
        return blocks.ring_kv_live_bytes(
            live, self.paged_ring_blocks_for(self.paged_block_size)
            * self.paged_block_size, self.kv_bytes_per_token())

    @staticmethod
    def tiny(**kw):
        """The CPU tests' size: every mechanism, no published width."""
        base = dict(vocab_size=128, hidden_size=64, num_hidden_layers=5,
                    num_attention_heads=8, num_key_value_heads=2, head_dim=16,
                    layer_types=(WINDOW, WINDOW, WINDOW, GLOBAL, WINDOW),
                    mlp_layer_types=("dense",) + ("sparse",) * 4,
                    intermediate_size=128, moe_intermediate_size=32,
                    num_experts=32, num_experts_per_tok=4,
                    sliding_window=8, max_position_embeddings=256,
                    selection_bias_std=0.01)
        base.update(kw)
        return ExaoneMoeConfig(**base)


class ExaoneAttention(nn.Module):
    config: ExaoneMoeConfig
    window: bool = False

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
        # BEFORE any rotation, in both kinds of layer
        q = blocks.RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="q_norm")(q)
        k = blocks.RMSNorm(cfg.rms_norm_eps, cfg.dtype, name="k_norm")(k)
        pos = blocks.call_positions(cfg, paging, t)
        if self.window:
            # a global layer is NOT rotated: its positions only address
            # the cache
            cos, sin = blocks.rope_frequencies(dh, pos, cfg.rope_theta)
            if cos.shape[0] == 1:
                cos, sin = cos[0], sin[0]
            q = blocks.apply_rope(q, cos, sin)
            k = blocks.apply_rope(k, cos, sin)
        if not cfg.serving:
            y = blocks.causal_gqa(q, k, v,
                                  cfg.sliding_window if self.window else 0)
        else:
            y, pools = self._paged(q, k, v, pos, paging, pools, index, work)
        out = proj("o_proj", cfg.hidden_size)(y.reshape(b, t, heads * dh))
        return out, pools

    def _paged(self, q, k, v, pos, paging, pools, index, work):
        """Write this step's keys and values where the kind of layer keeps
        them, and attend: a global layer through the sequence's block table
        (``blocks.paged_gqa``, a chunk's keys a tile at a time), a window
        layer in the slot's ring, the table's last entries
        (``blocks.ring_gqa``)."""
        cfg = self.config
        tables = paging["block_tables"]
        seq_blocks = tables.shape[-1] - cfg.paged_ring_blocks_for(
            cfg.paged_block_size)
        kind = "window" if self.window else "global"
        names = f"{kind}_key_pool", f"{kind}_value_pool"
        if self.window:
            y, k_pool, v_pool = blocks.ring_gqa(
                q, k, v, pos, paging, tables[:, seq_blocks:],
                pools[names[0]], pools[names[1]], index, "exaone_window",
                work=work, window=cfg.sliding_window)
        else:
            table_keys = seq_blocks * cfg.paged_block_size
            y, k_pool, v_pool = blocks.paged_gqa(
                q, k, v, pos, paging, tables[:, :seq_blocks],
                pools[names[0]], pools[names[1]], index, "exaone_global",
                work=work, key_tile=min(CHUNK_KEY_TILE, table_keys // 2))
        return y, {**pools, names[0]: k_pool, names[1]: v_pool}


def SparseExperts(config, **kw):
    """The sparse FFN of a config that says its own routing
    (``sparse_ffn()``): ``blocks.SparseFFN``, by the name the benchmark's
    families build it under."""
    return blocks.SparseFFN(**config.sparse_ffn(), **kw)


class ExaoneMoeForCausalLM(blocks.PagedDecoder):
    """``blocks.PagedDecoder`` over the two layer patterns, an untied
    head."""

    config: ExaoneMoeConfig

    def pool_shapes(self, num_blocks, block_size):
        """A key and a value pool a KIND of layer, each ``[layers of the
        kind, blocks, block_size, kv_heads * head_dim]``. The global pool
        has the engine's ``num_blocks``; the window pool the garbage block
        and a ring a slot."""
        cfg = self.config
        ring = cfg.paged_ring_blocks_for(block_size)
        lanes = cfg.num_key_value_heads * cfg.head_dim
        shapes = {}
        for kind, window, blocks_ in (("global", False, num_blocks), (
                "window", True, 1 + cfg.paged_ring_slots * ring)):
            layers = len(cfg.layers_of(window))
            if layers:
                row = (layers, blocks_, block_size, lanes)
                shapes[f"{kind}_key_pool"] = row
                shapes[f"{kind}_value_pool"] = row
        return shapes

    def step_work(self, paging):
        """The kernels' grids follow this step's lengths, the same for
        every layer of a kind: ``{window: work list}``."""
        from deepspeed_tpu.ops.hybrid_decode_attention import (
            hybrid_plan, hybrid_work_list)

        cfg = self.config
        bs = cfg.paged_block_size
        ring = cfg.paged_ring_blocks_for(bs)
        lanes = cfg.num_key_value_heads * cfg.head_dim
        tables, lengths = paging["block_tables"], paging["lengths"]

        def work(blocks_):
            # a slot is idle where its SEQUENCE's table says so (the
            # leading, global part of ``tables``): its ring is its own
            # whether it is busy or not
            return hybrid_work_list(
                lengths, tables, hybrid_plan(bs, lanes, lanes, blocks_))

        return {False: work(tables.shape[-1] - ring),
                True: ring and work(ring)}

    def mixer(self, i, u, paging, pools, work):
        cfg = self.config
        window = cfg.layer_types[i] == WINDOW
        return ExaoneAttention(cfg, window, name=f"layers_{i}_attn")(
            u, paging, pools, cfg.layers_of(window).index(i),
            work and work[window])
