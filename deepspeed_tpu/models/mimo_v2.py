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

RMSNorm, RoPE and SwiGLU are ``models/llama.py``'s. Layers are unrolled
(two kinds of attention and two of FFN do not scan).

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
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models.decode_utils import (paged_positions,
                                               paged_write_slots)
from deepspeed_tpu.models.llama import (LlamaMLP, RMSNorm, apply_rope,
                                        rope_frequencies)
from deepspeed_tpu.moe import dropless

_NEG = -1e30
# queries a chunk of the masked XLA attention of a whole prompt: 64 heads x
# 512 queries x 4096 keys of float32 scores are 0.5 GB
_QUERY_CHUNK = 512


@dataclasses.dataclass(frozen=True)
class MiMoV2Config:
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

    # what the generic serving code asks of a model's config
    @property
    def n_head(self) -> int:
        return self.num_attention_heads

    @property
    def rotary_dim(self) -> int:
        return int(self.head_dim * self.partial_rotary_factor) // 2 * 2

    @property
    def routed_width(self) -> int:
        """Experts a token chooses over all its sparse layers: the width
        of a row of what ``paged_return_routed`` returns."""
        return sum(self.moe_layer_freq) * self.num_experts_per_tok

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
        return -(-self.sliding_window // block_size) + 1

    def paged_slot_state_for(self, block_size: int):
        """What a decode slot keeps beside its block table (the engine's
        per-slot seam, ``serving/engine.py``): its ring, ``entries``
        blocks of the window pool. None without window layers."""
        ring = self.paged_ring_blocks_for(block_size)
        if not ring:
            return None
        return {"entries": ring, "knob": "ring_slots",
                "what": "sliding-window layers keep their keys and values "
                        "in a ring a decode slot"}

    def kv_live_bytes(self, live) -> dict:
        """Bytes of keys and values a decode step reads, by kind of layer,
        for busy rows of the lengths ``live``: a global layer every token
        of a sequence, a window layer what the slot's ring holds."""
        per_token = self.kv_bytes_per_token()
        held = (self.paged_ring_blocks_for(self.paged_block_size)
                * self.paged_block_size)
        return {"global": int(live.sum()) * per_token["global"],
                "window": int(np.minimum(live, held).sum())
                * per_token["window"]}

    def kv_bytes_per_token(self) -> dict:
        """Bytes of keys and values one token keeps, by kind of layer."""
        item = jnp.dtype(self.dtype).itemsize
        return {kind: len(self.layers_of(window)) * self.kv_heads(window)
                * (self.head_dim + self.v_head_dim) * item
                for kind, window in (("global", False), ("window", True))}

    def for_paged_decode(self, num_blocks: int, block_size: int,
                         kv_dtype: str = "", ring_slots: int = 0,
                         return_routed: bool = False):
        """Serving variant (see the module's docstring). ``num_blocks``
        sizes the global pool (block 0 the garbage block); ``ring_slots``
        decode slots get a ring each in the window pool; with
        ``return_routed`` a call also returns every token's chosen
        experts (``MiMoV2ForCausalLM``)."""
        if kv_dtype:
            raise ValueError(
                f"kv_cache_dtype {kv_dtype!r}: this model's two kinds of "
                "KV row have no quantized pool yet")
        if self.layers_of(True) and ring_slots < 1:
            raise ValueError("window layers keep a ring a decode slot: "
                             "for_paged_decode needs ring_slots")
        return dataclasses.replace(
            self, decode=True, paged=True, paged_num_blocks=int(num_blocks),
            paged_block_size=int(block_size),
            paged_ring_slots=int(ring_slots),
            paged_return_routed=bool(return_routed))

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


def _init(scale=0.02):
    return nn.initializers.normal(stddev=scale)


def masked_gqa(q, k, v, q_pos, k_pos, k_valid=None, window: int = 0,
               sink=None):
    """Grouped-query attention in XLA, float32 softmax: ``q [B, T, H, dk]``
    over ``k [B, S, KV, dk]`` / ``v [B, S, KV, dv]``; query at ``q_pos [B,
    T]`` sees key at ``k_pos [B, S]`` where ``k_pos <= q_pos``, inside the
    window if there is one, and ``k_valid``. ``sink [H]``: one more term
    ``exp(sink)`` in the denominator, with no value. -> ``[B, T, H, dv]``."""
    b, t, heads, dk = q.shape
    kv = k.shape[2]
    group = heads // kv
    s = jnp.einsum("btkgd,bskd->bkgts", q.reshape(b, t, kv, group, dk), k,
                   preferred_element_type=jnp.float32) * dk ** -0.5
    seen = k_pos[:, None, :] <= q_pos[:, :, None]
    if window:
        seen = seen & (k_pos[:, None, :] > q_pos[:, :, None] - window)
    if k_valid is not None:
        seen = seen & k_valid[:, None, :]
    seen = seen[:, None, None]                                   # [B,1,1,T,S]
    s = jnp.where(seen, s, _NEG)
    m = jnp.max(s, axis=-1, keepdims=True)
    if sink is not None:
        sk = sink.astype(jnp.float32).reshape(1, kv, group, 1, 1)
        m = jnp.maximum(m, sk)
    p = jnp.where(seen, jnp.exp(s - m), 0.0)
    denom = jnp.sum(p, axis=-1, keepdims=True)
    if sink is not None:
        denom = denom + jnp.exp(sk - m)
    out = jnp.einsum("bkgts,bskd->btkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    denom = jnp.where(denom == 0.0, 1.0, denom)
    out = out / denom.transpose(0, 3, 1, 2, 4)
    return out.reshape(b, t, heads, v.shape[-1]).astype(q.dtype)


def causal_gqa(q, k, v, window: int = 0, sink=None):
    """A whole sequence from position 0 over its own keys (training-style
    forward, a prompt's prefill): the masked XLA path, in pieces that fit.
    Window layers of a long sequence attend block by block against the
    block before and their own (a band of ``2 x window`` keys a query
    block); global layers in chunks of queries."""
    b, t = q.shape[:2]
    pos = jnp.broadcast_to(jnp.arange(t, dtype=jnp.int32)[None], (b, t))
    if window and t % window == 0 and t > 2 * window:
        n = t // window

        def blocks(x):
            return x.reshape(b * n, window, *x.shape[2:])

        def with_previous(x):
            x = x.reshape(b, n, window, *x.shape[2:])
            prev = jnp.concatenate([jnp.zeros_like(x[:, :1]), x[:, :-1]], 1)
            return jnp.concatenate([prev, x], 2).reshape(
                b * n, 2 * window, *x.shape[3:])

        # positions of the band: the block before (negative before the
        # first block: masked) and the block itself
        base = (jnp.arange(n, dtype=jnp.int32) * window)[None, :, None]
        band = jnp.arange(-window, window, dtype=jnp.int32)[None, None]
        k_pos = jnp.broadcast_to(base + band, (b, n, 2 * window)).reshape(
            b * n, 2 * window)
        out = masked_gqa(blocks(q), with_previous(k), with_previous(v),
                         blocks(pos), k_pos, k_pos >= 0, window, sink)
        return out.reshape(b, t, *out.shape[2:])
    if t > 2 * _QUERY_CHUNK and t % _QUERY_CHUNK == 0:
        n = t // _QUERY_CHUNK

        def chunk(args):
            qc, pc = args
            return masked_gqa(qc, k, v, pc, pos, None, window, sink)

        qs = q.reshape(b, n, _QUERY_CHUNK, *q.shape[2:]).swapaxes(0, 1)
        ps = pos.reshape(b, n, _QUERY_CHUNK).swapaxes(0, 1)
        out = jax.lax.map(chunk, (qs, ps))
        return out.swapaxes(0, 1).reshape(b, t, *out.shape[3:])
    return masked_gqa(q, k, v, pos, pos, None, window, sink)


class HybridAttention(nn.Module):
    config: MiMoV2Config
    window: bool = False

    @nn.compact
    def __call__(self, x, paging=None, pools=None, index=0, work=None):
        cfg = self.config
        b, t, _ = x.shape
        heads, kv = cfg.num_attention_heads, cfg.kv_heads(self.window)
        dk, dv = cfg.head_dim, cfg.v_head_dim

        def proj(name, width):
            return nn.Dense(width, use_bias=False, dtype=cfg.dtype,
                            param_dtype=cfg.param_dtype, kernel_init=_init(),
                            name=name)

        q = proj("q_proj", heads * dk)(x).reshape(b, t, heads, dk)
        k = proj("k_proj", kv * dk)(x).reshape(b, t, kv, dk)
        v = proj("v_proj", kv * dv)(x).reshape(b, t, kv, dv)
        v = (v.astype(jnp.float32) * cfg.attention_value_scale).astype(v.dtype)
        sink = None
        if (cfg.add_swa_attention_sink_bias if self.window
                else cfg.add_full_attention_sink_bias):
            sink = self.param("sink", _init(1.0), (heads,), cfg.param_dtype)
        window = cfg.sliding_window if self.window else 0

        paged = cfg.decode and cfg.paged
        if paged and paging is None:
            raise ValueError(
                "paged decode needs the `paging` call argument: "
                '{"block_tables", "lengths", "num_valid", "prefill"}')
        pos = (paged_positions(paging["lengths"], t) if paged
               else jnp.arange(t, dtype=jnp.int32)[None])
        rd = cfg.rotary_dim
        cos, sin = rope_frequencies(
            rd, pos, cfg.swa_rope_theta if self.window else cfg.rope_theta)
        if cos.shape[0] == 1:
            cos, sin = cos[0], sin[0]

        def rotate(u):
            return jnp.concatenate(
                [apply_rope(u[..., :rd], cos, sin), u[..., rd:]], axis=-1)

        q, k = rotate(q), rotate(k)
        if not paged:
            y = causal_gqa(q, k, v, window, sink)
        else:
            y, pools = self._paged(q, k, v, pos, paging, pools, index, work,
                                   window, sink)
        out = proj("o_proj", cfg.hidden_size)(y.reshape(b, t, heads * dv))
        return out, pools

    def _paged(self, q, k, v, pos, paging, pools, index, work, window, sink):
        """Write this step's keys and values where the kind of layer keeps
        them, and attend. A whole prompt (``paging["prefill"]``) attends
        over its own keys; a decode step on a TPU runs the paged kernel; a
        prompt's later chunk, and every step where no TPU is, gathers the
        sequence's blocks (or the slot's ring and the step's own rows) and
        takes the masked XLA path."""
        from deepspeed_tpu.ops.attention import (record_dispatch,
                                                 use_decode_kernel)
        from deepspeed_tpu.ops.hybrid_decode_attention import (
            decode_attention_hybrid, ring_positions)

        cfg = self.config
        b, t = q.shape[:2]
        kv = cfg.kv_heads(self.window)
        bs = cfg.paged_block_size
        ring = cfg.paged_ring_blocks_for(bs)
        tables, lengths = paging["block_tables"], paging["lengths"]
        num_valid = paging["num_valid"]
        seq_blocks = tables.shape[-1] - ring
        kind = "window" if self.window else "global"
        k_pool, v_pool = pools[f"{kind}_key_pool"], pools[f"{kind}_value_pool"]

        if self.window:
            table = tables[:, seq_blocks:]
            # of this step's rows the ring keeps the last (ring - 1) blocks'
            # worth: enough for the window, and never two rows on one place
            kept = pos >= (lengths + num_valid)[:, None] - (ring - 1) * bs
            real = (jnp.arange(t)[None] < num_valid[:, None]) & kept
            blk = jnp.where(real, jnp.take_along_axis(
                table, (pos // bs) % ring, axis=1), 0)
            off = pos % bs
        else:
            table = tables[:, :seq_blocks]
            blk, off = paged_write_slots(table, pos, num_valid, bs)

        def write():
            return (k_pool.at[index, blk, off].set(k.reshape(b, t, -1)),
                    v_pool.at[index, blk, off].set(v.reshape(b, t, -1)))

        def gathered(pool, width):
            """The table's blocks of this layer, as rows in table order."""
            return pool[index, table].reshape(b, -1, kv, width)

        if paging.get("prefill"):
            record_dispatch(f"mimo_{kind}_prefill_xla")
            k_pool, v_pool = write()
            y = causal_gqa(q, k, v, window, sink)
        elif t == 1 and use_decode_kernel():
            record_dispatch(f"mimo_{kind}_decode_kernel")
            k_pool, v_pool = write()
            with jax.named_scope("attn._hybrid_kv_attend"):
                y = decode_attention_hybrid(
                    q, k_pool, v_pool, table, lengths, index, kv_heads=kv,
                    window=window, ring=self.window, sink=sink, work=work)
        elif self.window:
            record_dispatch("mimo_window_cached_xla")
            # the ring as it stood BEFORE this step's rows, then the rows
            # themselves: a chunk's own writes would land on keys its
            # first queries still need
            held = ring_positions(lengths, ring * bs)
            y = masked_gqa(
                q, jnp.concatenate([gathered(k_pool, cfg.head_dim), k], 1),
                jnp.concatenate([gathered(v_pool, cfg.v_head_dim), v], 1),
                pos, jnp.concatenate([held, pos], 1),
                jnp.concatenate([held >= 0, jnp.arange(t)[None]
                                 < num_valid[:, None]], 1), window, sink)
            k_pool, v_pool = write()
        else:
            record_dispatch("mimo_global_cached_xla")
            k_pool, v_pool = write()
            rows = seq_blocks * bs
            key_pos = jnp.broadcast_to(
                jnp.arange(rows, dtype=jnp.int32)[None], (b, rows))
            y = masked_gqa(q, gathered(k_pool, cfg.head_dim),
                           gathered(v_pool, cfg.v_head_dim), pos, key_pos,
                           None, 0, sink)
        return y, {**pools, f"{kind}_key_pool": k_pool,
                   f"{kind}_value_pool": v_pool}


class SparseExperts(nn.Module):
    """The sparse FFN: the router over ALL published experts, the expert
    weights of the share held here (``moe/dropless.py``). Takes the
    float32 norm and returns the float32 sum of the held experts' terms,
    the layer's counters and the experts each token chose ``[B, T, k]``."""

    config: MiMoV2Config

    @nn.compact
    def __call__(self, x, valid=None):
        cfg = self.config
        b, t, d = x.shape
        first, count = dropless.held_range(cfg.n_routed_experts, cfg.ep_rank,
                                           cfg.ep_size)
        f = cfg.moe_intermediate_size
        router = self.param("router", _init(), (d, cfg.n_routed_experts),
                            cfg.param_dtype)
        bias = self.param("router_bias", _init(cfg.selection_bias_std),
                          (cfg.n_routed_experts,), cfg.param_dtype)
        gate = self.param("gate", _init(), (count, d, f), cfg.param_dtype)
        up = self.param("up", _init(), (count, d, f), cfg.param_dtype)
        down = self.param("down", _init(), (count, f, d), cfg.param_dtype)
        rows = x.reshape(b * t, d)
        # the gate reads the float32 norm itself, the experts its cfg.dtype
        # (the two constants of the normalisation are another family's:
        # models/lfm2_moe.py; absent, they add nothing to the program)
        experts, weights = dropless.route(
            rows, router, bias, cfg.num_experts_per_tok,
            norm_eps=getattr(cfg, "route_norm_eps", 0.0),
            scale=float(getattr(cfg, "routed_scaling_factor", 1.0)))
        rows = rows.astype(cfg.dtype)
        y, counters = dropless.expert_ffn(
            rows, experts, weights, gate.astype(cfg.dtype),
            up.astype(cfg.dtype), down.astype(cfg.dtype),
            first_expert=first, n_routed=cfg.n_routed_experts,
            valid=None if valid is None else valid.reshape(b * t))
        return y.reshape(b, t, d), counters, experts.reshape(b, t, -1)


def _paged_pools(module, cfg: MiMoV2Config):
    """The serving KV pools, declared once by the model: a key and a value
    pool a KIND of layer, each ``[layers of the kind, blocks, block_size,
    kv_heads * width]``. The global pool has the engine's ``num_blocks``;
    the window pool the garbage block and a ring a slot."""
    nb, bs = cfg.paged_num_blocks, cfg.paged_block_size
    if nb <= 1 or bs <= 0:
        raise ValueError(f"paged decode needs paged_num_blocks > 1 (got "
                         f"{nb}) and paged_block_size > 0 (got {bs})")
    ring = cfg.paged_ring_blocks_for(bs)
    pools = {}
    for kind, window, blocks in (("global", False, nb), (
            "window", True, 1 + cfg.paged_ring_slots * ring)):
        layers = len(cfg.layers_of(window))
        if not layers:
            continue
        for name, width in (("key", cfg.head_dim), ("value", cfg.v_head_dim)):
            pools[f"{kind}_{name}_pool"] = module.variable(
                "cache", f"{kind}_{name}_pool", jnp.zeros,
                (layers, blocks, bs, cfg.kv_heads(window) * width), cfg.dtype)
    return pools


class MiMoV2ForCausalLM(nn.Module):
    """Embedding -> the layer pattern -> final RMSNorm -> untied head.
    Plain call: ``[B, T, vocab]`` float32 logits. Paged (serving) call:
    ``(logits, {"counters": int32[4]})``, the sparse layers' counters of
    this call summed (``moe/dropless.COUNTERS``), which the serving
    programs hand back with the tokens; under ``paged_return_routed``
    also ``"routed": int32[B, T, sparse layers x k]``, the experts every
    token chose, layer by layer (a padded row's are meaningless)."""

    config: MiMoV2Config
    # what the serving engine's ledger names the counters by
    serve_counters = dropless.COUNTERS
    # for_paged_decode takes ``return_routed``
    serve_routed = True

    @nn.compact
    def __call__(self, input_ids, deterministic=True, paging=None):
        cfg = self.config
        paged = cfg.decode and cfg.paged
        embed = self.param("embed_tokens", _init(),
                           (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        x = embed[input_ids].astype(cfg.dtype)
        b, t = input_ids.shape
        pools = valid = None
        work = {False: None, True: None}
        if paged:
            variables = _paged_pools(self, cfg)
            pools = {name: var.value for name, var in variables.items()}
            tables, lengths = paging["block_tables"], paging["lengths"]
            # a bucket's padding and an idle slot's row are no tokens: they
            # route nowhere
            valid = ((jnp.arange(t)[None] < paging["num_valid"][:, None])
                     & (tables[:, :1] != 0))
            if t == 1 and not paging.get("prefill"):
                from deepspeed_tpu.ops.attention import use_decode_kernel
                from deepspeed_tpu.ops.hybrid_decode_attention import (
                    hybrid_work_list)

                if use_decode_kernel():
                    # the kernels' grids follow this step's lengths, the
                    # same for every layer of a kind: made once
                    bs = cfg.paged_block_size
                    ring = cfg.paged_ring_blocks_for(bs)
                    work = {False: hybrid_work_list(
                                lengths, bs, tables.shape[-1] - ring),
                            True: ring and hybrid_work_list(lengths, bs,
                                                            ring)}
        place = {i: n for window in (False, True)
                 for n, i in enumerate(cfg.layers_of(window))}
        counters = jnp.zeros((len(dropless.COUNTERS),), jnp.int32)
        routed = []
        # the residual stream and every norm are float32 (bfloat16 would
        # round the stream once a layer, and a norm's rounding moves the
        # router's near ties); what a matmul reads is cfg.dtype
        x = x.astype(jnp.float32)
        norm = lambda name: RMSNorm(cfg.layernorm_epsilon, jnp.float32,
                                    name=name)
        for i in range(cfg.num_hidden_layers):
            window = bool(cfg.hybrid_layer_pattern[i])
            scope = f"layers_{i}"
            a, pools = HybridAttention(cfg, window, name=f"{scope}_attn")(
                norm(f"{scope}_input_layernorm")(x).astype(cfg.dtype),
                paging, pools, place[i], work[window])
            x = x + a.astype(jnp.float32)
            h = norm(f"{scope}_post_attention_layernorm")(x)
            if cfg.moe_layer_freq[i]:
                y, c, chosen = SparseExperts(cfg, name=f"{scope}_mlp")(
                    h, valid)
                counters = counters + c
                routed.append(chosen)
            else:
                y = LlamaMLP(cfg, name=f"{scope}_mlp")(h.astype(cfg.dtype))
            x = x + y.astype(jnp.float32)
        if paged:
            for name, var in variables.items():
                var.value = pools[name]
        x = norm("norm")(x).astype(cfg.dtype)
        head = self.param("lm_head", _init(),
                          (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        logits = jnp.einsum("btc,vc->btv", x, head.astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
        if not paged:
            return logits
        aux = {"counters": counters}
        if cfg.paged_return_routed and routed:
            aux["routed"] = jnp.concatenate(routed, axis=-1)
        return logits, aux
