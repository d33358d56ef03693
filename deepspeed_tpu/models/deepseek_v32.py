"""The DeepSeek-V3.2 family (``model_type: deepseek_v32``): multi-head
latent attention with the QUERY compressed too, which reads only the keys
a learned INDEXER picks, over a dense or a grouped-sigmoid sparse FFN.

Layer ``i``, pre-norm, RMSNorm with a learned weight, no bias but the
indexer's LayerNorm: ``h = x + Attn(norm x)``, ``out = h + FFN_i(norm h)``.

- Attention. ``c_q = RMSNorm(x W_qa)`` (``q_lora_rank``); ``q = c_q W_qb``,
  a head ``[q_nope | q_pe]``; ``[c_kv | k_pe] = x W_kva``, ``c =
  RMSNorm(c_kv)``; a head's ``[k_nope | v] = c W_kvb``; ``k_pe`` ONE row for
  all heads; ``q_pe``, ``k_pe`` rotated as ``deepseek_v2.rotate_pairs`` does
  (interleaved pairs, YaRN's frequencies); ``s[t, h, j] = (q_nope . k_nope +
  q_pe . k_pe) * (nope + rope) ** -0.5 * m ** 2``, ``m = 0.1 * mscale_all_dim
  * ln(factor) + 1``; SOFTMAX OVER ``j`` IN ``S_t`` ONLY, in float32; ``o =
  concat_h(softmax v) W_o``.
- The indexer, a layer, from the same ``x`` and the same ``c_q``: ``qI = c_q
  W_Iq`` (``index_n_heads`` heads of ``index_head_dim``); ``kI = LayerNorm(x
  W_Ik)`` (weight AND bias), ONE row a token for all its heads; the FIRST
  ``qk_rope_head_dim`` values of each rotated at the same frequencies BY
  HALVES (``x1, x2 = split(x, 2)``: not interleaved), the rest not; ``w = (x
  W_Iw) * heads ** -0.5 * index_head_dim ** -0.5``; ``I[t, j] = sum_h w[t,
  h] relu(qI[t, h] . kI[j])`` for ``j <= t``, float32; ``S_t`` the ``min(
  index_topk, t + 1)`` positions of largest ``I[t, j]``, equal scores to the
  lower ``j`` (``ops/dsa_index_select.py``: exact).
- FFN: SwiGLU for ``i < first_k_dense_replace``; else ``s = sigmoid(x
  W_r)`` over ALL published experts, ``c = s + bias``, a group's score the
  sum of its two largest ``c``, the ``topk_group`` best of ``n_group``
  groups stay, the ``k`` largest ``c`` inside them chosen, weights
  ``routed_scaling_factor * s / sum s``; the held experts' terms and one
  shared expert (``blocks.SparseFFN``, ``moe/dropless.py``).
- a final norm; an untied head.

READINGS (the published ``config.json`` gives keys, not code; these are the
V3.2-Exp report's equations and its published ``inference/model.py``,
``Indexer`` and ``MLA``): the ReLU and the two scales on ``w``; the
LayerNorm on the index key; the indexer's rotated part FIRST and by halves
where the main path rotates interleaved pairs; the indexer's query read
from the query's latent. ASSUMED: index keys kept in the serving type
without the published code's Hadamard rotation and FP8 (an orthogonal
rotation of both sides leaves ``q . k`` as it was; FP8 is a storage
precision the config does not state); the tie rule.

WHAT A TOKEN KEEPS, a layer: the latent row ``[c | k_pe]`` (576 values, in
640 lanes: ``deepseek_v2``'s) and the indexer's own row ``kI`` (128
values). SERVING: ``for_paged_decode`` gives the module TWO pools,
``latent_pool [layers, blocks, block_size, 640]`` and ``index_pool [layers,
blocks, block_size, 128]``, both addressed through the ONE block table.
Every call writes its rows to both, scores its queries against the
sequence's index rows, selects, and attends the chosen:

- a prompt's chunk (and a whole prompt, and the plain call): DECOMPRESSED
  UNDER THE SELECTION'S MASK, the sequence's latent rows a tile at a time
  through ``W_kvb`` (``deepseek_v2``'s loop and online softmax), a query's
  unchosen keys masked: exact, and no position is ever sorted;
- a decode step: ABSORBED over the ``index_topk`` GATHERED rows
  (``ops/dsa_sparse_attend.py``): what a step reads of a sequence is every
  live index row and at most ``index_topk`` latent rows.

Every paged call also returns the chosen sets (``"selected"``: ``uint32[B,
T, layers, keys / 32]``, key ``j`` bit ``j % 32`` of word ``j // 32``), which
the engine fetches only for a request that asks
(``submit(.., keep_selected=True)``), and counts ``dsa_keys_live`` and
``dsa_keys_selected`` (the keys its real queries could and did attend, all
layers).
"""

import dataclasses
import functools
import math
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.models import blocks
from deepspeed_tpu.models.decode_utils import paged_write_slots
from deepspeed_tpu.models.deepseek_v2 import (YarnScaling, _online_softmax,
                                              absorbed_halves, pool_row,
                                              rotate_pairs, yarn_frequencies,
                                              yarn_mscale)
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops import dsa_index_select as select_op
from deepspeed_tpu.ops import dsa_sparse_attend as attend_op

_NEG = -1e30
# keys a tile of the XLA attention and score loops (whole 32-key words)
_KEY_TILE = 1024
# what the program counts itself, behind the sparse layers' four
DSA_COUNTERS = ("dsa_keys_live", "dsa_keys_selected")


@dataclasses.dataclass(frozen=True)
class DeepseekV32Config(blocks.ServedConfig):
    vocab_size: int = 129280
    hidden_size: int = 7168
    num_hidden_layers: int = 61
    num_attention_heads: int = 128
    q_lora_rank: int = 1536
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    index_n_heads: int = 64
    index_head_dim: int = 128
    index_topk: int = 2048
    intermediate_size: int = 18432
    first_k_dense_replace: int = 3
    moe_intermediate_size: int = 2048
    n_routed_experts: int = 256           # the router's width: ALL experts
    n_shared_experts: int = 1
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    routed_scaling_factor: float = 2.5
    # the experts held here: rank ep_rank of ep_size equal contiguous shares
    ep_rank: int = 0
    ep_size: int = 1
    # what the selection bias is drawn with (training moves it from zero)
    selection_bias_std: float = 0.0
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_scaling: Optional[YarnScaling] = YarnScaling(
        mscale=1.0, mscale_all_dim=1.0)
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
        if self.qk_rope_head_dim % 2 or (self.qk_rope_head_dim
                                         > self.index_head_dim):
            raise ValueError(
                f"qk_rope_head_dim {self.qk_rope_head_dim} rotates pairs, "
                f"the first of index_head_dim {self.index_head_dim}")

    unquantized = "latent rows have no quantized pool"

    def sparse(self, i: int) -> bool:
        return i >= self.first_k_dense_replace

    def sparse_ffn(self) -> dict:
        return dict(experts=self.n_routed_experts,
                    top_k=self.num_experts_per_tok,
                    width=self.moe_intermediate_size, scoring="sigmoid",
                    renormalize=True, scale=self.routed_scaling_factor,
                    bias_std=self.selection_bias_std, n_group=self.n_group,
                    topk_group=self.topk_group,
                    shared_width=(self.n_shared_experts
                                  * self.moe_intermediate_size),
                    ep_rank=self.ep_rank, ep_size=self.ep_size,
                    dtype=self.dtype, param_dtype=self.param_dtype)

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """Values a token keeps a layer for attention: ``[c | k_pe]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self) -> int:
        return -(-self.latent_row // 128) * 128

    @property
    def softmax_scale(self) -> float:
        scale = self.qk_head_dim ** -0.5
        if self.rope_scaling is not None:
            m = yarn_mscale(self.rope_scaling.factor,
                            self.rope_scaling.mscale_all_dim)
            scale = scale * m * m
        return scale

    @property
    def index_scale(self) -> float:
        return self.index_n_heads ** -0.5 * self.index_head_dim ** -0.5

    def kv_bytes_per_token(self) -> dict:
        """Bytes one token KEEPS, all layers, by kind of row: the latent
        row as it is counted (576 values, whatever lanes the pool pads to)
        and the indexer's row."""
        each = self.num_hidden_layers * jnp.dtype(self.dtype).itemsize
        return {"latent": each * self.latent_row,
                "index": each * self.index_head_dim}

    def kv_live_bytes(self, live) -> dict:
        """Bytes of per-sequence state a decode step READS, for busy rows
        of the lengths ``live``: every live token's index row, and the
        latent rows of the ``index_topk`` tokens chosen (all of them where
        a row has no more)."""
        per = self.kv_bytes_per_token()
        return {"latent": int(np.minimum(live, self.index_topk).sum())
                * per["latent"],
                "index": int(live.sum()) * per["index"]}

    def paged_row_kind(self) -> dict:
        return {"kind": "latent",
                "what": f"block pool keeps one latent row a token "
                        f"({self.latent_row} values shared by all "
                        f"{self.num_attention_heads} heads, no keys and "
                        f"values by heads) and an index row beside it "
                        f"({self.index_head_dim} values, which choose the "
                        f"{self.index_topk} keys a query attends)"}

    @staticmethod
    def tiny(**kw):
        """The CPU tests' size: every mechanism, no published width."""
        base = dict(vocab_size=128, hidden_size=64, num_hidden_layers=2,
                    num_attention_heads=4, q_lora_rank=48, kv_lora_rank=128,
                    qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                    index_n_heads=4, index_head_dim=128, index_topk=16,
                    intermediate_size=128, first_k_dense_replace=1,
                    moe_intermediate_size=32, n_routed_experts=16,
                    n_shared_experts=1, num_experts_per_tok=4, n_group=4,
                    topk_group=2, max_position_embeddings=4096,
                    selection_bias_std=0.02,
                    rope_scaling=YarnScaling(
                        factor=8.0, mscale=1.0, mscale_all_dim=1.0,
                        original_max_position_embeddings=64))
        base.update(kw)
        return DeepseekV32Config(**base)


def rotate_first_halves(x, positions, cfg: DeepseekV32Config):
    """The indexer's rotation: the FIRST ``qk_rope_head_dim`` values of ``x
    [B, T, ..., index_head_dim]`` at ``positions [B, T]``, by halves (``x1,
    x2 = split(x_rope, 2)``; the main path's pairs are interleaved), the
    rest as they are."""
    rope = cfg.qk_rope_head_dim
    inv, factor = yarn_frequencies(rope, cfg.rope_theta, cfg.rope_scaling)
    angle = positions.astype(jnp.float32)[..., None] * inv
    cos, sin = jnp.cos(angle) * factor, jnp.sin(angle) * factor
    while cos.ndim < x.ndim:
        cos, sin = cos[..., None, :], sin[..., None, :]
    a, b = (x[..., :rope // 2].astype(jnp.float32),
            x[..., rope // 2:rope].astype(jnp.float32))
    return jnp.concatenate(
        [(a * cos - b * sin).astype(x.dtype),
         (b * cos + a * sin).astype(x.dtype), x[..., rope:]], axis=-1)


def _tile_for(keys: int, block_size: int = 1) -> int:
    """Keys a tile of the loops over ``keys`` keys: whole blocks, whole
    32-key words, at most ``_KEY_TILE`` (more where a block asks)."""
    unit = block_size * 32 // math.gcd(block_size, 32)
    return max(unit, min(_KEY_TILE // unit * unit, -(-keys // unit) * unit))


class SparseLatentAttention(nn.Module):
    config: DeepseekV32Config

    @nn.compact
    def __call__(self, x, paging=None, pools=None, index=0):
        """-> ``(term, pools, (mask [B, T, words], live [B, T], chosen [B,
        T]))``: the chosen sets packed, and the keys each query could and
        did attend."""
        cfg = self.config
        b, t, _ = x.shape
        heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
        nope, rope, dv = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                          cfg.v_head_dim)
        proj = functools.partial(blocks.dense, cfg)
        with jax.named_scope("dsa.q_latent"):
            c_q = blocks.RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                                 name="q_a_layernorm")(
                proj("q_a_proj", cfg.q_lora_rank)(x))
        q = proj("q_b_proj", heads * cfg.qk_head_dim)(c_q).reshape(
            b, t, heads, cfg.qk_head_dim)
        kva = proj("kv_a_proj_with_mqa", rank + rope)(x)
        c = blocks.RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                           name="kv_a_layernorm")(kva[..., :rank])
        w_kvb = self.param("kv_b_proj", blocks.init(),
                           (rank, heads * (nope + dv)),
                           cfg.param_dtype).astype(cfg.dtype).reshape(
                               rank, heads, nope + dv)
        pos = blocks.call_positions(cfg, paging, t)
        if not cfg.serving:
            pos = jnp.broadcast_to(pos, (b, t))
        q_nope = q[..., :nope]
        q_pe = rotate_pairs(q[..., nope:], pos, cfg)
        k_pe = rotate_pairs(kva[..., rank:], pos, cfg)
        # the indexer: its queries off the query's latent, its one key row
        with jax.named_scope("dsa.index_projections"):
            q_i = rotate_first_halves(
                proj("index_q_proj", cfg.index_n_heads * cfg.index_head_dim)(
                    c_q).reshape(b, t, cfg.index_n_heads,
                                 cfg.index_head_dim), pos, cfg)
            k_i = rotate_first_halves(
                nn.LayerNorm(epsilon=1e-6, dtype=cfg.dtype,
                             param_dtype=jnp.float32, name="index_k_norm")(
                    proj("index_k_proj", cfg.index_head_dim)(x)), pos, cfg)
            w_i = jnp.einsum(
                "btc,ch->bth", x,
                self.param("index_weights_proj", blocks.init(),
                           (cfg.hidden_size, cfg.index_n_heads),
                           cfg.param_dtype).astype(cfg.dtype),
                preferred_element_type=jnp.float32) * cfg.index_scale
        row = pool_row(c, k_pe, cfg.latent_lanes)
        if not cfg.serving:
            y, seen = self._chunk(
                q_nope, q_pe, q_i, w_i, w_kvb, pos,
                jnp.full((b,), t, jnp.int32), *self._own_rows(row, k_i, t))
        else:
            y, pools, seen = self._paged(q_nope, q_pe, q_i, w_i, w_kvb, row,
                                         k_i, pos, paging, pools, index)
        out = proj("o_proj", cfg.hidden_size)(y.reshape(b, t, heads * dv))
        return out, pools, seen

    # ---- where a tile of keys comes from: (latent rows of tile j, index
    # rows of tile j), the tile's keys, the tiles there are, the keys in all
    def _own_rows(self, row, k_i, t):
        """A call's OWN rows (a whole sequence from position 0)."""
        tile = _tile_for(t)
        pad = -t % tile
        row, k_i = (jnp.pad(u, ((0, 0), (0, pad), (0, 0)))
                    for u in (row, k_i))

        def cut(u):
            return lambda j: jax.lax.dynamic_slice_in_dim(u, j * tile, tile,
                                                          1)
        return cut(row), cut(k_i), tile, (t + pad) // tile, t + pad

    def _pool_rows(self, pools, index, table, live):
        """The rows a block table addresses, in both pools: whole blocks,
        as many tiles as the longest live prefix of the call has
        (traced)."""
        bs = self.config.paged_block_size
        mb = table.shape[-1]
        tile = _tile_for(mb * bs, bs)
        per = tile // bs
        # the table in whole tiles (the garbage block behind it)
        table = jnp.pad(table, ((0, 0), (0, -mb % per)))

        def cut(pool):
            def rows(j):
                at = jax.lax.dynamic_slice_in_dim(table, j * per, per, 1)
                got = pool[index, at].reshape(table.shape[0], tile, -1)
                # what lies past a row's live prefix weighs 0, and 0 x
                # whatever it holds (NaN included) must stay 0
                k_pos = j * tile + jnp.arange(tile, dtype=jnp.int32)
                return jnp.where((k_pos[None] < live[:, None])[..., None],
                                 got, jnp.zeros_like(got))
            return rows
        return (cut(pools["latent_pool"]), cut(pools["index_pool"]), tile,
                (jnp.max(live) + tile - 1) // tile, table.shape[-1] * bs)

    # ---- the selection and the two forms of the attention over it
    def _chunk(self, q_nope, q_pe, q_i, w_i, w_kvb, pos, live, latent_rows,
               index_rows, tile, tiles, cap):
        """Many queries a row: the selection as a mask, the DECOMPRESSED
        attention under it (one row on a TPU: the kernel)."""
        from deepspeed_tpu.ops.attention import record_dispatch

        cfg = self.config
        b, t = pos.shape
        nope, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        scores = select_op.index_scores(q_i, w_i, index_rows, tiles, tile,
                                        cap)

        # causal and live: a query may choose from a prefix of the keys
        could = jnp.minimum(pos + 1, live[:, None])
        mask, chosen, bias = select_op.select_mask(
            scores.reshape(b * t, cap), could.reshape(b * t),
            cfg.index_topk, jnp.max(live))
        mask = mask.reshape(b, t, -1)

        seen = (mask, could, chosen.reshape(b, t))
        if b == 1 and attend_op.kernel_serves(
                t, cfg.num_attention_heads, nope, cfg.qk_rope_head_dim,
                cfg.v_head_dim, rank, cfg.latent_lanes, cap):
            record_dispatch("dsa_chunk_masked_decompressed_kernel")
            # the row's latent rows side by side, as many tiles as are live
            rows = jax.lax.fori_loop(
                0, tiles, lambda j, out: jax.lax.dynamic_update_slice_in_dim(
                    out, latent_rows(j)[0], j * tile, 0),
                jnp.zeros((cap, cfg.latent_lanes), q_nope.dtype))
            with jax.named_scope("dsa_sparse_attend.masked"):
                out = attend_op.attend_masked(
                    q_nope[0], q_pe[0], rows, w_kvb, mask[0], jnp.max(live),
                    scale=cfg.softmax_scale, bias=bias)
            return out[None], seen
        record_dispatch("dsa_chunk_masked_decompressed_xla")

        def tile_of(j):
            got = latent_rows(j)
            c, k_pe = got[..., :rank], got[..., rank:cfg.latent_row]
            with jax.named_scope("mla._decompress"):
                kv = jnp.einsum("bsc,chd->bshd", c, w_kvb)
            s = (jnp.einsum("bthd,bshd->bhts", q_nope, kv[..., :nope],
                            preferred_element_type=jnp.float32)
                 + jnp.einsum("bthr,bsr->bhts", q_pe, k_pe,
                              preferred_element_type=jnp.float32))
            # an unchosen key is no key: under the online softmax's own
            # mask its score weighs exp(-1e30 - max) = 0
            s = jnp.where(attend_op.mask_tile(mask, j, tile)[:, None],
                          s * cfg.softmax_scale, _NEG)
            v = kv[..., nope:]
            return s, lambda p: jnp.einsum(
                "bhts,bshd->bhtd", p.astype(v.dtype), v,
                preferred_element_type=jnp.float32)

        with jax.named_scope("dsa_sparse_attend.masked"):
            out = _online_softmax(tiles, tile_of, pos, live,
                                  cfg.num_attention_heads, cfg.v_head_dim,
                                  tile)
        return out.transpose(0, 2, 1, 3).astype(q_nope.dtype), seen

    def _step(self, q_nope, q_pe, q_i, w_i, w_kvb, pos, live, pools, index,
              table, index_rows, tile, tiles, cap):
        """One query a row: the selection as positions, the ABSORBED
        attention over their gathered rows."""
        cfg = self.config
        nope, rank = cfg.qk_nope_head_dim, cfg.kv_lora_rank
        scores = select_op.index_scores(q_i, w_i, index_rows, tiles, tile,
                                        cap)[:, 0]
        k_pos = jnp.arange(cap, dtype=jnp.int32)[None]
        at, chosen, mask = select_op.select_positions(
            scores, k_pos < live[:, None], cfg.index_topk)
        w_k, w_v = absorbed_halves(w_kvb, nope)
        q_full = pool_row(jnp.einsum("bthd,chd->bthc", q_nope, w_k), q_pe,
                          cfg.latent_lanes)
        o_lat = attend_op.attend_chosen_rows(
            q_full[:, 0], pools["latent_pool"], index,
            attend_op.pool_rows_of(at, table, cfg.paged_block_size),
            rank=rank, scale=cfg.softmax_scale)
        y = jnp.einsum("bthc,chd->bthd", o_lat[:, None], w_v)
        return y, (mask[:, None], live[:, None], chosen[:, None])

    def _paged(self, q_nope, q_pe, q_i, w_i, w_kvb, row, k_i, pos, paging,
               pools, index):
        """Write this call's rows to both pools through the block table,
        then select and attend through it."""
        from deepspeed_tpu.ops.attention import record_dispatch

        cfg = self.config
        b, t = pos.shape
        table, lengths = paging["block_tables"], paging["lengths"]
        num_valid = paging["num_valid"]
        blk, off = paged_write_slots(table, pos, num_valid,
                                     cfg.paged_block_size)
        with jax.named_scope("mla._latent_write"):
            pools = dict(
                pools,
                latent_pool=pools["latent_pool"].at[index, blk, off].set(
                    row.astype(pools["latent_pool"].dtype)),
                index_pool=pools["index_pool"].at[index, blk, off].set(
                    k_i.astype(pools["index_pool"].dtype)))
        live = lengths + num_valid
        source = self._pool_rows(pools, index, table, live)
        if t > 1 or paging.get("prefill"):
            y, seen = self._chunk(q_nope, q_pe, q_i, w_i, w_kvb, pos, live,
                                  *source)
        else:
            record_dispatch("dsa_decode_absorbed_gathered_xla")
            y, seen = self._step(q_nope, q_pe, q_i, w_i, w_kvb, pos, live,
                                 pools, index, table, *source[1:])
        return y, pools, seen


def SparseExperts(config, **kw):
    """The sparse FFN of a config (``blocks.SparseFFN`` with this family's
    routing), by the name the benchmark's family builds it under."""
    return blocks.SparseFFN(**config.sparse_ffn(), **kw)


class DeepseekV32ForCausalLM(blocks.PagedDecoder):
    """``blocks.PagedDecoder`` over sparse latent attention, an untied
    head."""

    config: DeepseekV32Config
    serve_counters = dropless.COUNTERS + DSA_COUNTERS
    # every paged call returns its chosen sets (``"selected"``)
    serve_selected = True

    def pool_shapes(self, num_blocks, block_size):
        """The two serving pools, a row a token a layer each, through the
        one block table."""
        cfg = self.config
        lead = (cfg.num_hidden_layers, num_blocks, block_size)
        return {"latent_pool": lead + (cfg.latent_lanes,),
                "index_pool": lead + (cfg.index_head_dim,)}

    def step_work(self, paging):
        return None     # no kernel's grid follows the step's lengths

    def mixer(self, i, u, paging, pools, work):
        held = None
        if pools is not None:
            held = {k: v for k, v in pools.items() if k.endswith("_pool")}
        a, held, seen = SparseLatentAttention(
            self.config, name=f"layers_{i}_attn")(u, paging, held, i)
        if pools is None:
            return a, None
        # (beside the pools, which the shell writes back by name: each
        # layer's chosen sets, which the shell hands back as ``"selected"``,
        # and the keys its queries could and did attend)
        mask, could, chosen = seen
        return a, dict(
            held, selected=pools.get("selected", ()) + (mask,),
            dsa_keys=pools.get("dsa_keys", ()) + ((could, chosen),))

    def more_counters(self, routed, valid, pools):
        """``dsa_keys_live`` and ``dsa_keys_selected`` of the call's real
        queries, all layers."""
        could, chosen = zip(*pools["dsa_keys"])
        return jnp.stack([jnp.sum(jnp.where(valid, n, 0), dtype=jnp.int32)
                          for n in (sum(could), sum(chosen))])
