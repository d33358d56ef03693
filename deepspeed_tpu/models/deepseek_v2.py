"""The DeepSeek-V2 family: multi-head LATENT attention over a dense or a
sparse FFN.

Layer ``i``, pre-norm, RMSNorm with a learned weight, no bias anywhere:
``h = x + Attn(norm x)``, ``out = h + FFN_i(norm h)``.

- Attention (no query compression: ``q_lora_rank`` null). ``q = x W_q``,
  a head ``[q_nope (128) | q_pe (64)]``; ``[c_kv (512) | k_pe (64)] = x
  W_kva``; ``c = RMSNorm(c_kv)``; a head's ``[k_nope (128) | v (128)] = c
  W_kvb``; ``k_pe`` is ONE row for all heads. ``q_pe`` and ``k_pe`` are
  rotated: the pairs ``(x_2i, x_2i+1)`` de-interleaved, then the
  half-rotation, at YaRN's frequencies (:func:`yarn_frequencies`).
  ``scores = (q_nope . k_nope + q_pe . k_pe) * (128 + 64) ** -0.5 * m ** 2``
  with ``m = 0.1 * mscale_all_dim * ln(factor) + 1``; causal softmax in
  float32; ``o = concat_h(softmax v) W_o``.
- FFN: SwiGLU for ``i < first_k_dense_replace``; else ``p = softmax(x
  W_g)`` over the routed experts in float32, the ``k`` largest chosen
  (greedy: no groups, no bias), weights ``p`` itself times
  ``routed_scaling_factor`` (``norm_topk_prob`` false), ``sum_i p_i E_i(x)
  + S(x)``: dropless (``moe/dropless.py``, told which experts it holds),
  and ``S`` the ``n_shared_experts`` shared experts, one SwiGLU of their
  summed width, which every share of the experts computes alike.
- a final norm; an untied head.

The norms, SwiGLU, the sparse FFN and the decoder shell are
``models/blocks.py``'s; this file holds the config, YaRN, the latent
attention in its two forms and the latent pool.

WHAT A TOKEN KEEPS. One row a layer, ``[c | k_pe]`` after the norm and the
rotation: ``kv_lora_rank + qk_rope_head_dim`` values (576: 1,152 B in
bfloat16) shared by every head, where keys and values by heads would keep
``heads * (192 + 128)`` (5,120 values at 16 heads).

SERVING. ``for_paged_decode`` gives the module ONE pool, ``latent_pool
[layers, blocks, block_size, lanes]``, addressed through the sequence's
block table as every model's rows are; ``lanes`` is the row padded to
whole 128-lane registers (576 -> 640, the padding zeros: a row of 4.5
registers makes the backend lay the pool out its own way and Mosaic
refuses a DMA of half a register, PERF.md PRs 27 and 34), so the pool
takes a ninth more than it keeps. No state a decode slot. TWO attention
paths over the same rows, the same function:

- DECOMPRESSED (a whole prompt, a prefill chunk): the sequence's rows are
  gathered a tile of keys at a time, taken through ``W_kvb`` into keys and
  values by heads, and attended with an online softmax, as many tiles as
  the longest row of the call has (a traced count: a chunk at position
  1,024 does not pay for a table of 16,384). 10.2 kFLOP a query-key pair.
- ABSORBED (a decode step): ``W_kvb``'s key half folded into the query
  (``q_lat[h] = q_nope[h] W_K[h]``) and its value half into the output
  (``o[h] = (softmax c) W_V[h]``), the step is multi-query attention over
  the rows as they lie: ``ops/latent_decode_attention.py`` on a TPU, the
  same tiles in XLA elsewhere. 34.8 kFLOP a pair, and a ninth of the
  bytes, which is what a decode step is bound by.
"""

import dataclasses
import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models import blocks
from deepspeed_tpu.models.decode_utils import paged_write_slots
from deepspeed_tpu.moe import dropless

_NEG = -1e30
# keys a tile of the XLA attention paths: 16 heads x 512 queries x 1024
# keys of float32 scores are 32 MB, their decompressed keys and values 8 MB
_KEY_TILE = 1024


@dataclasses.dataclass(frozen=True)
class YarnScaling:
    """``rope_scaling`` of ``type: yarn`` as the published config has it."""
    factor: float = 40.0
    beta_fast: float = 32.0
    beta_slow: float = 1.0
    mscale: float = 0.707
    mscale_all_dim: float = 0.707
    original_max_position_embeddings: int = 4096


@dataclasses.dataclass(frozen=True)
class DeepseekV2Config(blocks.ServedConfig):
    vocab_size: int = 102400
    hidden_size: int = 2048
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 10944
    first_k_dense_replace: int = 1
    moe_intermediate_size: int = 1408
    n_routed_experts: int = 64            # the router's width: ALL experts
    n_shared_experts: int = 2
    num_experts_per_tok: int = 6
    routed_scaling_factor: float = 1.0
    # the experts held here: rank ep_rank of ep_size equal contiguous shares
    ep_rank: int = 0
    ep_size: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnScaling] = YarnScaling()
    max_position_embeddings: int = 163840
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    # serving (for_paged_decode)
    decode: bool = False
    paged: bool = False
    paged_num_blocks: int = 0
    paged_block_size: int = 0
    paged_return_routed: bool = False

    def __post_init__(self):
        dropless.held_range(self.n_routed_experts, self.ep_rank,
                            self.ep_size)
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim {self.qk_rope_head_dim} "
                             "rotates pairs")

    # the contract's (blocks.ServedConfig): why kv_dtype is refused (no
    # state a decode slot), the layers that are sparse and their routing
    unquantized = "latent rows have no quantized pool"

    def sparse(self, i: int) -> bool:
        return i >= self.first_k_dense_replace

    def sparse_ffn(self) -> dict:
        return dict(experts=self.n_routed_experts,
                    top_k=self.num_experts_per_tok,
                    width=self.moe_intermediate_size, scoring="softmax",
                    renormalize=False, scale=self.routed_scaling_factor,
                    shared_width=(self.n_shared_experts
                                  * self.moe_intermediate_size),
                    ep_rank=self.ep_rank, ep_size=self.ep_size,
                    dtype=self.dtype, param_dtype=self.param_dtype)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """Values a token keeps a layer: ``[c | k_pe]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self) -> int:
        """Lanes of a pool row: the row in whole 128-lane registers."""
        return -(-self.latent_row // 128) * 128

    @property
    def softmax_scale(self) -> float:
        scale = self.qk_head_dim ** -0.5
        if self.rope_scaling is not None:
            m = yarn_mscale(self.rope_scaling.factor,
                            self.rope_scaling.mscale_all_dim)
            scale = scale * m * m
        return scale

    def kv_bytes_per_token(self) -> dict:
        """Bytes one token keeps, all layers: the latent rows as they are
        COUNTED (576 values a layer), whatever lanes the pool pads to."""
        return {"latent": self.num_hidden_layers * self.latent_row
                * jnp.dtype(self.dtype).itemsize}

    def kv_live_bytes(self, live) -> dict:
        """Bytes of per-sequence state a decode step reads, for busy rows
        of the lengths ``live``: every token's latent row in every layer."""
        return {"latent": int(live.sum())
                * self.kv_bytes_per_token()["latent"]}

    def paged_row_kind(self) -> dict:
        """What a row of this model's block pool is, for the serving
        mechanisms that know only keys and values by heads (the engine's
        seam, ``serving/engine.py``: each refuses the model by name)."""
        return {"kind": "latent",
                "what": f"block pool keeps one latent row a token "
                        f"({self.latent_row} values shared by all "
                        f"{self.num_attention_heads} heads, no keys and "
                        "values by heads)"}

    @staticmethod
    def tiny(**kw):
        """The CPU tests' size: every mechanism, no published width."""
        base = dict(vocab_size=128, hidden_size=64, num_hidden_layers=4,
                    num_attention_heads=4, kv_lora_rank=128,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    intermediate_size=128, first_k_dense_replace=1,
                    moe_intermediate_size=32, n_routed_experts=16,
                    n_shared_experts=2, num_experts_per_tok=3,
                    max_position_embeddings=4096,
                    rope_scaling=YarnScaling(
                        factor=8.0, original_max_position_embeddings=64))
        base.update(kw)
        return DeepseekV2Config(**base)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(dim: int, theta: float,
                     scaling: Optional[YarnScaling]):
    """``(inverse frequencies [dim / 2], the factor on cos and sin)``:
    YaRN's blend of the published frequencies (rotations that fit the
    original context more than ``beta_fast`` times keep theirs; fewer than
    ``beta_slow`` times, theirs over ``factor``; a linear ramp between)."""
    exps = jnp.arange(0, dim, 2, dtype=jnp.float32) / dim
    extra = 1.0 / theta ** exps
    if scaling is None:
        return extra, 1.0

    def correction_dim(rotations):
        return (dim * math.log(scaling.original_max_position_embeddings
                               / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(scaling.beta_fast)), 0)
    high = min(math.ceil(correction_dim(scaling.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0.0, 1.0)
    inv = (extra / scaling.factor) * ramp + extra * (1.0 - ramp)
    return inv, (yarn_mscale(scaling.factor, scaling.mscale)
                 / yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def rotate_pairs(x, positions, cfg: DeepseekV2Config):
    """``x [B, T, ..., rope]`` at ``positions [B, T]``: the interleaved
    pairs ``(x_2i, x_2i+1)`` brought to ``[evens | odds]`` and rotated by
    halves. Queries and keys come out in the same order, so their product
    is the published one."""
    inv, factor = yarn_frequencies(cfg.qk_rope_head_dim, cfg.rope_theta,
                                   cfg.rope_scaling)
    angle = positions.astype(jnp.float32)[..., None] * inv     # [B, T, r/2]
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    while cos.ndim < x.ndim:
        cos, sin = cos[..., None, :], sin[..., None, :]
    a, b = (x[..., 0::2].astype(jnp.float32),
            x[..., 1::2].astype(jnp.float32))
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin],
                           axis=-1).astype(x.dtype)


def _online_softmax(tiles, tile_of, q_pos, live, heads: int, width: int,
                    tile: int):
    """Causal attention by tiles of keys, float32: ``tile_of(j) -> (s [B, H,
    T, tile] scaled scores, weigh)`` for keys at positions ``[j * tile, (j
    + 1) * tile)``, ``weigh(p [B, H, T, tile]) -> [B, H, T, width]`` their
    values' sum. Query ``t`` of row ``b`` at ``q_pos[b, t]`` sees the keys
    at positions up to its own and under ``live[b]`` (the row's live
    prefix: what lies behind it in the pool is never read into a sum).
    ``tiles`` may be traced. -> ``[B, H, T, width]`` float32."""
    b, t = q_pos.shape

    def body(j, carry):
        m, l, acc = carry
        s, weigh = tile_of(j)
        k_pos = j * tile + jnp.arange(tile, dtype=jnp.int32)
        seen = ((k_pos[None, None] <= q_pos[:, :, None])
                & (k_pos[None, None] < live[:, None, None]))[:, None]
        s = jnp.where(seen, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1))
        p = jnp.where(seen, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m - m_new)
        return (m_new, alpha * l + jnp.sum(p, axis=-1),
                acc * alpha[..., None] + weigh(p))

    init = (jnp.full((b, heads, t), _NEG, jnp.float32),
            jnp.zeros((b, heads, t), jnp.float32),
            jnp.zeros((b, heads, t, width), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, tiles, body, init)
    return acc / jnp.where(l == 0.0, 1.0, l)[..., None]


def pool_row(c, k_pe, lanes: int):
    """The row a token keeps in the pool: ``[c | k_pe | zeros]`` after the
    norm and the rotation, ``lanes`` wide."""
    pad = lanes - c.shape[-1] - k_pe.shape[-1]
    return jnp.concatenate(
        [c, k_pe, jnp.zeros(c.shape[:-1] + (pad,), c.dtype)], axis=-1)


def absorbed_halves(w_kvb, nope: int):
    """``W_kvb [rank, heads, nope + dv]`` as the absorbed decode step takes
    it: ``(W_K [rank, heads, nope], W_V [rank, heads, dv])``, the half
    folded into the query and the half folded into the output."""
    return w_kvb[..., :nope], w_kvb[..., nope:]


class LatentAttention(nn.Module):
    config: DeepseekV2Config

    @nn.compact
    def __call__(self, x, paging=None, pool=None, index=0, work=None):
        cfg = self.config
        b, t, _ = x.shape
        heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        proj = functools.partial(blocks.dense, cfg)
        q = proj("q_proj", heads * cfg.qk_head_dim)(x).reshape(
            b, t, heads, cfg.qk_head_dim)
        kva = proj("kv_a_proj_with_mqa", rank + rope)(x)
        c = blocks.RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                           name="kv_a_layernorm")(kva[..., :rank])
        # [rank, heads, nope + dv]: a head's key half, then its value half
        w_kvb = self.param("kv_b_proj", blocks.init(),
                           (rank, heads * (nope + dv)),
                           cfg.param_dtype).astype(cfg.dtype).reshape(
                               rank, heads, nope + dv)
        paged = cfg.serving
        pos = blocks.call_positions(cfg, paging, t)
        if not paged:
            pos = jnp.broadcast_to(pos, (b, t))
        q_nope = q[..., :nope]
        q_pe = rotate_pairs(q[..., nope:], pos, cfg)
        k_pe = rotate_pairs(kva[..., rank:], pos, cfg)
        if not paged:
            y = self._decompressed(q_nope, q_pe, w_kvb, pos,
                                   jnp.full((b,), t, jnp.int32),
                                   self._own_rows(c, k_pe, t))
        else:
            y, pool = self._paged(q_nope, q_pe, c, k_pe, w_kvb, pos, paging,
                                  pool, index, work)
        out = proj("o_proj", cfg.hidden_size)(y.reshape(b, t, heads * dv))
        return out, pool

    # ---- where a tile of keys comes from
    @staticmethod
    def _own_rows(c, k_pe, t):
        """Tiles of a call's OWN rows (a whole sequence from position 0)."""
        tile = min(_KEY_TILE, t)
        pad = -t % tile
        c, k_pe = (jnp.pad(u, ((0, 0), (0, pad), (0, 0))) for u in (c, k_pe))

        def rows(j):
            return (jax.lax.dynamic_slice_in_dim(c, j * tile, tile, 1),
                    jax.lax.dynamic_slice_in_dim(k_pe, j * tile, tile, 1))
        return rows, tile, (t + pad) // tile

    def _pool_rows(self, pool, index, table, live):
        """Tiles of the rows a block table addresses: whole blocks, as many
        tiles as the longest live prefix of the call has (traced)."""
        cfg = self.config
        bs, rank = cfg.paged_block_size, cfg.kv_lora_rank
        mb = table.shape[-1]
        per = max(1, min(_KEY_TILE // bs, mb))
        tile = per * bs
        # the table in whole tiles (the garbage block behind it)
        table = jnp.pad(table, ((0, 0), (0, -mb % per)))

        def rows(j):
            blocks = jax.lax.dynamic_slice_in_dim(table, j * per, per, 1)
            got = pool[index, blocks].reshape(table.shape[0], tile, -1)
            # what lies past a row's live prefix weighs 0, and 0 x whatever
            # it holds (NaN included) must stay 0
            k_pos = j * tile + jnp.arange(tile, dtype=jnp.int32)
            got = jnp.where((k_pos[None] < live[:, None])[..., None], got,
                            jnp.zeros_like(got))
            return got[..., :rank], got[..., rank:cfg.latent_row]
        return rows, tile, (jnp.max(live) + tile - 1) // tile

    # ---- the two forms of the same attention
    def _decompressed(self, q_nope, q_pe, w_kvb, pos, live, source):
        """Keys and values by heads, a tile of rows at a time through
        ``W_kvb``."""
        cfg = self.config
        nope = cfg.qk_nope_head_dim
        rows, tile, tiles = source

        def tile_of(j):
            c, k_pe = rows(j)
            with jax.named_scope("mla._decompress"):
                kv = jnp.einsum("bsc,chd->bshd", c, w_kvb)
            s = (jnp.einsum("bthd,bshd->bhts", q_nope, kv[..., :nope],
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bthr,bsr->bhts", q_pe, k_pe,
                              preferred_element_type=jnp.float32))
            v = kv[..., nope:]
            return s * cfg.softmax_scale, lambda p: jnp.einsum(
                "bhts,bshd->bhtd", p.astype(v.dtype), v,
                preferred_element_type=jnp.float32)

        out = _online_softmax(tiles, tile_of, pos, live,
                              cfg.num_attention_heads, cfg.v_head_dim, tile)
        return out.transpose(0, 2, 1, 3).astype(q_nope.dtype)

    def absorbed_query(self, q_nope, q_pe, w_k):
        """``[q_nope W_K | q_pe | zeros]`` a head, ``latent_lanes`` wide:
        the query a latent row is scored against as it lies."""
        q_lat = jnp.einsum("bthd,chd->bthc", q_nope, w_k)
        return pool_row(q_lat, q_pe, self.config.latent_lanes)

    def _absorbed_xla(self, q_full, pos, live, source):
        """The decode kernel's arithmetic in XLA (where no TPU is): every
        head against the rows as they lie, the values their first lanes."""
        cfg = self.config
        rank = cfg.kv_lora_rank
        rows, tile, tiles = source

        def tile_of(j):
            c, k_pe = rows(j)
            s = (jnp.einsum("bthc,bsc->bhts", q_full[..., :rank], c,
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bthr,bsr->bhts",
                              q_full[..., rank:cfg.latent_row], k_pe,
                              preferred_element_type=jnp.float32))
            return s * cfg.softmax_scale, lambda p: jnp.einsum(
                "bhts,bsc->bhtc", p.astype(c.dtype), c,
                preferred_element_type=jnp.float32)

        out = _online_softmax(tiles, tile_of, pos, live,
                              cfg.num_attention_heads, rank, tile)
        return out.transpose(0, 2, 1, 3).astype(q_full.dtype)

    def _paged(self, q_nope, q_pe, c, k_pe, w_kvb, pos, paging, pool, index,
               work):
        """Write this call's latent rows through the block table and
        attend: a whole prompt over its own rows and a prompt's later chunk
        over the sequence's gathered rows, both DECOMPRESSED; a decode step
        ABSORBED, on a TPU through the latent kernel."""
        from deepspeed_tpu.ops.attention import (record_dispatch,
                                                 use_decode_kernel)
        from deepspeed_tpu.ops.latent_decode_attention import (
            decode_attention_latent)

        cfg = self.config
        b, t = pos.shape
        nope, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        table, lengths = paging["block_tables"], paging["lengths"]
        num_valid = paging["num_valid"]
        blk, off = paged_write_slots(table, pos, num_valid,
                                     cfg.paged_block_size)
        with jax.named_scope("mla._latent_write"):
            pool = pool.at[index, blk, off].set(
                pool_row(c, k_pe, cfg.latent_lanes).astype(pool.dtype))
        live = lengths + num_valid
        if paging.get("prefill"):
            record_dispatch("mla_prefill_decompressed_xla")
            y = self._decompressed(q_nope, q_pe, w_kvb, pos, live,
                                   self._own_rows(c, k_pe, t))
        elif t > 1:
            record_dispatch("mla_chunk_decompressed_xla")
            y = self._decompressed(q_nope, q_pe, w_kvb, pos, live,
                                   self._pool_rows(pool, index, table, live))
        else:
            w_k, w_v = absorbed_halves(w_kvb, nope)
            q_full = self.absorbed_query(q_nope, q_pe, w_k)
            if use_decode_kernel():
                record_dispatch("mla_decode_absorbed_kernel")
                with jax.named_scope("attn._latent_kv_attend"):
                    o_lat = decode_attention_latent(
                        q_full, pool, table, lengths, index, rank=rank,
                        scale=cfg.softmax_scale, work=work)
            else:
                record_dispatch("mla_decode_absorbed_xla")
                o_lat = self._absorbed_xla(
                    q_full, pos, live,
                    self._pool_rows(pool, index, table, live))
            y = jnp.einsum("bthc,chd->bthd", o_lat, w_v)
        return y, pool


def SparseExperts(config, **kw):
    """The sparse FFN of a config: ``blocks.SparseFFN`` with this family's
    routing (``DeepseekV2Config.sparse_ffn``), by the name the benchmark's
    family builds it under. -> ``(the held experts' terms, the shared
    experts' term, counters, chosen)``."""
    return blocks.SparseFFN(**config.sparse_ffn(), **kw)


class DeepseekV2ForCausalLM(blocks.PagedDecoder):
    """``blocks.PagedDecoder`` over latent attention, an untied head."""

    config: DeepseekV2Config

    def pool_shapes(self, num_blocks, block_size):
        """The one serving pool: a latent row a token a layer, through the
        block table."""
        cfg = self.config
        return {"latent_pool": (cfg.num_hidden_layers, num_blocks,
                                block_size, cfg.latent_lanes)}

    def step_work(self, paging):
        """The kernel's grid follows this step's lengths, the same for
        every layer."""
        from deepspeed_tpu.ops.latent_decode_attention import latent_step_work

        cfg = self.config
        return latent_step_work(paging["lengths"], paging["block_tables"],
                                cfg.paged_block_size, cfg.latent_lanes)

    def mixer(self, i, u, paging, pools, work):
        a, pool = LatentAttention(self.config, name=f"layers_{i}_attn")(
            u, paging, pools and pools["latent_pool"], i, work)
        return a, pools and {"latent_pool": pool}
