"""The MiMo-V2 family: hybrid window / global attention over a sparse FFN.

A decoder composed of attention KIND x FFN KIND x a per-layer pattern:

- attention: grouped-query heads with keys and queries wider than values
  (192 / 128 published), the leading ``rotary_dim`` of each head rotated
  (half-rotation), values scaled by a constant; ``hybrid_layer_pattern[l]``
  0 = global (its own KV-head count and RoPE base), 1 = sliding window
  (``sliding_window`` keys, inclusive of the query's own, a learnable sink a
  head in the softmax's denominator);
- FFN: ``moe_layer_freq[l]`` 0 = dense SwiGLU, 1 = dropless sigmoid top-k
  routing over ``n_routed_experts`` (``moe/dropless.py``) of which this
  rank holds ``n_routed_experts / ep_size``;
- pre-norm residual blocks, RMSNorm, no bias, an untied head.

The norms, RoPE, SwiGLU, the attention arithmetic, the sparse FFN and the
decoder shell are ``models/blocks.py``'s, the window kind's ring
(``blocks.ring_gqa``) with them; this file holds the config, the hybrid
attention and the two kinds' pools.
Layers are unrolled (two kinds of attention and two of FFN do not scan).

SERVING. ``for_paged_decode`` gives the module two pairs of KV pools, one
a kind of layer, each of the pool's one shape (``ops/decode_attention.py``:
``[layers, blocks, block_size, lanes]``, lanes whole registers):
``global_*_pool`` is addressed through the sequence's block table as
GPT-2's is; ``window_*_pool`` holds one RING of ``ring_blocks`` blocks a
decode slot, so its bytes depend on the slots and not on the context. The
engine hands one table a row: the sequence's blocks, then the slot's ring
(``serving/engine.py``). A ring is why a window layer was chosen over
freeing blocks behind the window: the table's width, the pool's size and
every program's shapes stay fixed, nothing is allocated or freed while a
request runs, and a position's place is arithmetic
(``ops/hybrid_decode_attention.py``).
"""

import dataclasses
import functools
from typing import Any, Tuple

import flax.linen as nn
import jax.numpy as jnp

from deepspeed_tpu.models import blocks
from deepspeed_tpu.moe import dropless


@dataclasses.dataclass(frozen=True)
class MiMoV2Config(blocks.ServedConfig):
    vocab_size: int = 152576
    hidden_size: int = 4096
    num_hidden_layers: int = 48
    num_attention_heads: int = 64
    num_key_value_heads: int = 4          # global layers
    swa_num_key_value_heads: int = 8      # window layers
    head_dim: int = 192                   # queries and keys
    v_head_dim: int = 128
    hybrid_layer_pattern: Tuple[int, ...] = ()   # 0 global, 1 window
    moe_layer_freq: Tuple[int, ...] = ()         # 0 dense, 1 sparse
    intermediate_size: int = 16384
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256           # the router's width: ALL experts
    num_experts_per_tok: int = 8
    # the experts held here: rank ep_rank of ep_size equal contiguous shares
    ep_rank: int = 0
    ep_size: int = 1
    sliding_window: int = 128
    rope_theta: float = 1e7
    swa_rope_theta: float = 1e4
    partial_rotary_factor: float = 0.334
    attention_value_scale: float = 0.707
    layernorm_epsilon: float = 1e-5
    add_swa_attention_sink_bias: bool = True
    add_full_attention_sink_bias: bool = False
    max_position_embeddings: int = 1048576
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
        if (len(self.hybrid_layer_pattern) != n
                or len(self.moe_layer_freq) != n):
            raise ValueError(
                f"hybrid_layer_pattern and moe_layer_freq need one entry a "
                f"layer ({n}), got {len(self.hybrid_layer_pattern)} and "
                f"{len(self.moe_layer_freq)}")
        dropless.held_range(self.n_routed_experts, self.ep_rank,
                            self.ep_size)

    # the contract's (blocks.ServedConfig): the slots' keyword, why
    # kv_dtype is refused, the layers that are sparse and their routing
    slot_knob = "ring_slots"
    unquantized = "two kinds of KV row have no quantized pool yet"

    def sparse(self, i: int) -> bool:
        return bool(self.moe_layer_freq[i])

    def sparse_ffn(self) -> dict:
        return dict(experts=self.n_routed_experts,
                    top_k=self.num_experts_per_tok,
                    width=self.moe_intermediate_size,
                    bias_std=self.selection_bias_std, ep_rank=self.ep_rank,
                    ep_size=self.ep_size, dtype=self.dtype,
                    param_dtype=self.param_dtype)

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor) // 2 * 2

    def kv_heads(self, window: bool) -> int:
        return (self.swa_num_key_value_heads if window
                else self.num_key_value_heads)

    def layers_of(self, window: bool):
        """Indices of the layers of one kind, in order: a layer's place in
        its kind's pool is its place here."""
        return [i for i, kind in enumerate(self.hybrid_layer_pattern)
                if bool(kind) == window]

    def paged_ring_blocks_for(self, block_size: int) -> int:
        """Blocks in a slot's ring: the window and one more, so that the
        block being written never holds a key the window still needs.
        0 without window layers."""
        if not self.layers_of(True):
            return 0
        return blocks.ring_blocks_for(self.sliding_window, block_size)

    def paged_slot_state_for(self, block_size: int):
        """What a decode slot keeps beside its block table (the engine's
        per-slot seam, ``serving/engine.py``): its ring, ``entries``
        blocks of the window pool. None without window layers."""
        return blocks.ring_slot_state(
            self.paged_ring_blocks_for(block_size), self.slot_knob)

    def kv_live_bytes(self, live) -> dict:
        """Bytes of keys and values a decode step reads, by kind of layer,
        for busy rows of the lengths ``live``: a global layer every token
        of a sequence, a window layer what the slot's ring holds."""
        return blocks.ring_kv_live_bytes(
            live, self.paged_ring_blocks_for(self.paged_block_size)
            * self.paged_block_size, self.kv_bytes_per_token())

    def kv_bytes_per_token(self) -> dict:
        """Bytes of keys and values one token keeps, by kind of layer."""
        item = jnp.dtype(self.dtype).itemsize
        return {kind: len(self.layers_of(window)) * self.kv_heads(window)
                * (self.head_dim + self.v_head_dim) * item
                for kind, window in (("global", False), ("window", True))}

    @staticmethod
    def tiny(**kw):
        """The CPU tests' size: every mechanism, no published width."""
        base = dict(vocab_size=128, hidden_size=64, num_hidden_layers=7,
                    num_attention_heads=8, num_key_value_heads=1,
                    swa_num_key_value_heads=2, head_dim=24, v_head_dim=16,
                    hybrid_layer_pattern=(0, 1, 1, 1, 1, 1, 0),
                    moe_layer_freq=(0, 1, 1, 1, 1, 1, 1),
                    intermediate_size=128, moe_intermediate_size=32,
                    n_routed_experts=32, num_experts_per_tok=4,
                    sliding_window=8, max_position_embeddings=256,
                    selection_bias_std=0.01)
        base.update(kw)
        return MiMoV2Config(**base)


class HybridAttention(nn.Module):
    config: MiMoV2Config
    window: bool = False

    @nn.compact
    def __call__(self, x, paging=None, pools=None, index=0, work=None):
        cfg = self.config
        b, t, _ = x.shape
        heads, kv = cfg.num_attention_heads, cfg.kv_heads(self.window)
        dk, dv = cfg.head_dim, cfg.v_head_dim

        proj = functools.partial(blocks.dense, cfg)
        q = proj("q_proj", heads * dk)(x).reshape(b, t, heads, dk)
        k = proj("k_proj", kv * dk)(x).reshape(b, t, kv, dk)
        v = proj("v_proj", kv * dv)(x).reshape(b, t, kv, dv)
        v = (v.astype(jnp.float32) * cfg.attention_value_scale).astype(v.dtype)
        sink = None
        if (cfg.add_swa_attention_sink_bias if self.window
                else cfg.add_full_attention_sink_bias):
            sink = self.param("sink", blocks.init(1.0), (heads,),
                              cfg.param_dtype)
        window = cfg.sliding_window if self.window else 0

        pos = blocks.call_positions(cfg, paging, t)
        rd = cfg.rotary_dim
        cos, sin = blocks.rope_frequencies(
            rd, pos, cfg.swa_rope_theta if self.window else cfg.rope_theta)
        if cos.shape[0] == 1:
            cos, sin = cos[0], sin[0]

        def rotate(u):
            return jnp.concatenate(
                [blocks.apply_rope(u[..., :rd], cos, sin), u[..., rd:]],
                axis=-1)

        q, k = rotate(q), rotate(k)
        if not cfg.serving:
            y = blocks.causal_gqa(q, k, v, window, sink)
        else:
            y, pools = self._paged(q, k, v, pos, paging, pools, index, work,
                                   sink)
        out = proj("o_proj", cfg.hidden_size)(y.reshape(b, t, heads * dv))
        return out, pools

    def _paged(self, q, k, v, pos, paging, pools, index, work, sink):
        """Write this step's keys and values where the kind of layer keeps
        them, and attend: a global layer through the sequence's block table
        (``blocks.paged_gqa``), a window layer in the slot's ring, the
        table's last entries (``blocks.ring_gqa``)."""
        cfg = self.config
        tables = paging["block_tables"]
        seq_blocks = tables.shape[-1] - cfg.paged_ring_blocks_for(
            cfg.paged_block_size)
        kind = "window" if self.window else "global"
        step = (functools.partial(blocks.ring_gqa,
                                  window=cfg.sliding_window)
                if self.window else blocks.paged_gqa)
        table = (tables[:, seq_blocks:] if self.window
                 else tables[:, :seq_blocks])
        y, k_pool, v_pool = step(
            q, k, v, pos, paging, table, pools[f"{kind}_key_pool"],
            pools[f"{kind}_value_pool"], index, f"mimo_{kind}", sink, work)
        return y, {**pools, f"{kind}_key_pool": k_pool,
                   f"{kind}_value_pool": v_pool}

def SparseExperts(config, **kw):
    """The sparse FFN of a config that says its own routing
    (``sparse_ffn()``): ``blocks.SparseFFN``, by the name the benchmark's
    families build it under."""
    return blocks.SparseFFN(**config.sparse_ffn(), **kw)


class MiMoV2ForCausalLM(blocks.PagedDecoder):
    """``blocks.PagedDecoder`` over the layer pattern, an untied head."""

    config: MiMoV2Config
    eps_field = "layernorm_epsilon"
    # the dense FFN's weights are float32 whatever ``param_dtype`` says, as
    # they were when the block was the Llama family's
    dense_param_dtype = jnp.float32

    def pool_shapes(self, num_blocks, block_size):
        """A key and a value pool a KIND of layer, each ``[layers of the
        kind, blocks, block_size, kv_heads * width]``. The global pool has
        the engine's ``num_blocks``; the window pool the garbage block and
        a ring a slot."""
        cfg = self.config
        ring = cfg.paged_ring_blocks_for(block_size)
        shapes = {}
        for kind, window, blocks_ in (("global", False, num_blocks), (
                "window", True, 1 + cfg.paged_ring_slots * ring)):
            layers = len(cfg.layers_of(window))
            if not layers:
                continue
            for name, width in (("key", cfg.head_dim),
                                ("value", cfg.v_head_dim)):
                shapes[f"{kind}_{name}_pool"] = (
                    layers, blocks_, block_size,
                    cfg.kv_heads(window) * width)
        return shapes

    def step_work(self, paging):
        """The kernels' grids follow this step's lengths, the same for
        every layer of a kind: ``{window: work list}``."""
        from deepspeed_tpu.ops.hybrid_decode_attention import (
            hybrid_plan, hybrid_work_list)

        cfg = self.config
        bs = cfg.paged_block_size
        ring = cfg.paged_ring_blocks_for(bs)
        tables, lengths = paging["block_tables"], paging["lengths"]

        def work(window, blocks):
            # a slot is idle where its SEQUENCE's table says so (the
            # leading, global part of ``tables``): its ring is its own
            # whether it is busy or not
            kv = cfg.kv_heads(window)
            plan = hybrid_plan(bs, kv * cfg.head_dim, kv * cfg.v_head_dim,
                               blocks)
            return hybrid_work_list(lengths, tables, plan)

        return {False: work(False, tables.shape[-1] - ring),
                True: ring and work(True, ring)}

    def mixer(self, i, u, paging, pools, work):
        cfg = self.config
        window = bool(cfg.hybrid_layer_pattern[i])
        return HybridAttention(cfg, window, name=f"layers_{i}_attn")(
            u, paging, pools, cfg.layers_of(window).index(i),
            work and work[window])
