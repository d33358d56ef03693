"""The Phi-4-mini-flash family (``phi4flash``, the "SambaY" design): a
SELF-DECODER of Mamba-1 and sliding-window attention layers that caches
ONCE, and a CROSS-DECODER of gated memory units and cross-attention layers
that own no cache: they read the self-decoder's.

``x_0 = E[ids]``; layer ``i``, pre-norm, LayerNorm with weight and bias:
``h = x + Mix_i(LN x)``, ``x' = h + MLP(LN h)`` (a bias-free SwiGLU every
layer); logits ``= LN(x_L) E^T`` (tied). NO positional encoding: the Mamba
layers carry position. With ``L`` layers and ``L / 2 = half``
(:meth:`Phi4FlashConfig.kind`):

- even ``i <= half``, ``"mamba"`` (Mamba-1; inner width ``C = expand x
  d``, ``N`` states a channel, ``K`` taps, ``R = dt_rank``): ``[x | z] = u
  W_in``; ``x <- silu(conv_K(x) + b)`` (depthwise, causal); ``[dt | B | C]
  = x W_x``; ``delta = softplus(dt W_dt + b_dt)``; ``h_t[c, n] =
  exp(delta_t[c] A[c, n]) h_{t-1}[c, n] + delta_t[c] B_t[n] x_t[c]``, ``y_t
  = sum_n C_t[n] h_t[., n] + D x_t``; ``Mix = (y * silu(z)) W_out``
  (``ops/mamba1_scan.py``). Layer ``half`` also hands on ``m = y`` (with
  the ``D`` term, before the gate): THE MEMORY.
- odd ``i < half``, ``"window"``: differential attention over the last
  ``sliding_window`` positions, the query's own among them.
- ``i = half + 1``, ``"full"``: differential attention over every position;
  its keys and values are THE CACHE, the only one a token keeps.
- even ``i > half``, ``"gmu"`` (gated memory unit): ``Mix = (m * silu(u
  W_1)) W_2``, ``m`` the memory at the same position. No state.
- odd ``i > half + 1``, ``"cross"``: queries of its own against the full
  layer's keys and values, causal, differential. No key, value or pool.
- differential attention (:class:`DiffAttention`): query heads ``(2j, 2j +
  1)`` are ``q1, q2``, KV heads ``(2m, 2m + 1)`` are ``k1, k2`` and ``v1,
  v2``, ``m = j // 2``; ``o_s = softmax(q_s k_s^T / sqrt(dk)) [v1 | v2]``,
  ``o = o_1 - lambda o_2``, ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) +
  lambda_init(i)``; then ``RMSNorm(o) (1 - lambda_init)`` over the pair's
  ``2 dk`` values, the pairs side by side into ``W_o``. Biases on ``W_qkv``
  / ``W_q`` and ``W_o``.

The norms' class, SwiGLU, the attention arithmetic (a head's VALUE GROUP,
``blocks.value_groups``), the ring, the causal convolution and the decoder
shell are ``models/blocks.py``'s; this file holds the config, the Mamba-1
mixer, the differential attention, the unit and the pools.

SERVING. ``for_paged_decode`` gives the module ONE ``global`` key pool and
value pool of ONE layer (``[1, blocks, block_size, kv_heads x dk]``),
written by the full layer through the sequence's block table and read by it
and by every cross layer; the window layers' ``window_*_pool``, a RING of
``ring_blocks`` blocks a decode slot (``blocks.ring_gqa``); and the Mamba
layers' two state pools, a row a slot behind row 0: ``ssm_state_pool
[layers, 1 + slots, C / L, N, L]`` float32 (lane groups of channels along
the lanes, the states down the sublanes) and ``ssm_conv_pool [layers, 1 +
slots, (K - 1) C]`` (the convolution's last rows side by side in ONE row).
A slot's table is the sequence's blocks, then its ring, then its state row:
the engine's per-slot seam in two parts under one knob
(``paged_slot_state_for``). A sequence at length 0 starts from zeros
whatever its slot held; a ring needs no cleaning.

A PREFILL STOPS AT THE CACHE. A paged call of ``T > 1`` runs the
self-decoder for its ``T`` tokens and the cross-decoder and the head for ONE
row a sequence, the one at ``num_valid - 1`` (``rows_from``): the layers
from ``half + 2`` on only read what the call has cached by then, and only a
prompt's last position is sampled. The call counts both (``cross_rows``,
``self_tokens``). A plain call runs every position through every layer.
"""

import dataclasses
import math
from typing import Any

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models import blocks
from deepspeed_tpu.models.decode_utils import embed_lookup
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops import mamba1_scan

# keys a tile of a prefill chunk's full attention (``blocks.cached_gqa``): 40
# heads x 512 queries x 1,024 keys of float32 scores are 84 MB
CHUNK_KEY_TILE = 1024
# a query pair keeps the values of two adjacent KV heads side by side
VALUE_GROUP = 2


@dataclasses.dataclass(frozen=True)
class Phi4FlashConfig(blocks.ServedConfig):
    vocab_size: int = 200064
    hidden_size: int = 2560
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    intermediate_size: int = 10240
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 0       # 0: ceil(hidden_size / 16)
    num_experts_per_tok: int = 0
    embedding_std: float = 0.02   # the tied embedding is drawn N(0, this)
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
        n, heads, kv = (self.num_hidden_layers, self.num_attention_heads,
                        self.num_key_value_heads)
        if self.mb_per_layer != 2 or n % 2 or n < 8:
            raise ValueError(
                f"mb_per_layer {self.mb_per_layer} over {n} layers: the "
                "phi4flash family implements Mamba-shaped layers at the even "
                "indices of an even depth of at least 8")
        if self.hidden_size % heads or heads % kv or heads % 4 or kv % 2:
            raise ValueError(
                f"{heads} heads over hidden {self.hidden_size} and {kv} KV "
                "heads: differential attention pairs adjacent heads of both")

    # the contract's (blocks.ServedConfig): the slots' keyword, why
    # kv_dtype is refused, and that no layer is sparse
    slot_knob = "state_slots"
    unquantized = ("ring, state-space state and the cache eight layers "
                   "share have no quantized pool")

    def sparse(self, i: int) -> bool:
        return False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self) -> int:
        return self.mamba_dt_rank or -(-self.hidden_size // 16)

    @property
    def half(self) -> int:
        return self.num_hidden_layers // 2

    def kind(self, i: int) -> str:
        """``"mamba" | "window" | "full" | "gmu" | "cross"``."""
        if i % 2 == 0:
            return "mamba" if i <= self.half else "gmu"
        if i < self.half:
            return "window"
        return "full" if i == self.half + 1 else "cross"

    def layers_of(self, kind: str):
        """Indices of the layers of one kind, in order: a layer's place in
        its kind's pools is its place here."""
        return [i for i in range(self.num_hidden_layers)
                if self.kind(i) == kind]

    @property
    def cache_readers(self) -> int:
        """Layers that read the ONE global pool: the full layer and every
        cross layer."""
        return 1 + len(self.layers_of("cross"))

    def lambda_init(self, i: int) -> float:
        return 0.8 - 0.6 * math.exp(-0.3 * i)

    def paged_ring_blocks_for(self, block_size: int) -> int:
        return blocks.ring_blocks_for(self.sliding_window, block_size)

    def paged_slot_state_for(self, block_size: int):
        """What a decode slot keeps beside its block table, in the table's
        order: its ring of the window pools, then its row of the two state
        pools (the engine's per-slot seam, two ``parts`` under one knob)."""
        ring = self.paged_ring_blocks_for(block_size)
        return {"entries": ring + 1, "parts": (ring, 1),
                "knob": self.slot_knob,
                "what": "sliding-window layers keep their keys and values "
                        "in a ring a decode slot, its state-space layers a "
                        "state of fixed size a decode slot, and eight layers "
                        "share the one cache a token keeps"}

    def kv_bytes_per_token(self) -> dict:
        """Bytes of keys and values one token KEEPS, by kind of layer: the
        one cache the full layer writes; a window layer's ring row."""
        row = (self.num_key_value_heads * 2 * self.head_dim
               * jnp.dtype(self.dtype).itemsize)
        return {"global": row,
                "window": len(self.layers_of("window")) * row}

    def state_bytes_per_slot(self) -> int:
        """Bytes a decode slot's state takes, all Mamba layers: ``C x N``
        float32 and the convolution's last rows."""
        c = self.mamba_inner
        return len(self.layers_of("mamba")) * (
            c * self.mamba_d_state * 4
            + (self.mamba_d_conv - 1) * c * jnp.dtype(self.dtype).itemsize)

    def kv_live_bytes(self, live) -> dict:
        """Bytes of per-sequence state a decode step READS, by kind, for
        busy rows of the lengths ``live``: the one global pool once for
        each layer that reads it, a window layer what the slot's ring
        holds, the Mamba layers' state a busy slot."""
        kept = self.kv_bytes_per_token()
        ring_rows = (self.paged_ring_blocks_for(self.paged_block_size)
                     * self.paged_block_size)
        read = blocks.ring_kv_live_bytes(
            live, ring_rows,
            {**kept, "global": kept["global"] * self.cache_readers})
        return {**read, "state": len(live) * self.state_bytes_per_slot()}

    @staticmethod
    def tiny(**kw):
        """The CPU tests' size: every mechanism, no published width: two
        Mamba / window pairs, the memory layer, the cache layer, one unit,
        one cross layer; a window shorter than the tests' prompts."""
        base = dict(vocab_size=128, hidden_size=64, num_hidden_layers=8,
                    num_attention_heads=8, num_key_value_heads=4,
                    intermediate_size=128, sliding_window=8,
                    mamba_d_state=8, max_position_embeddings=256)
        base.update(kw)
        return Phi4FlashConfig(**base)


# ---------------------------------------------------------------------------
# Mamba-1's own initialisers

def _a_log_init(key, shape, dtype):
    """``A[c, n] = -(n + 1)``: the S4D-real start, every channel alike."""
    del key
    return jnp.broadcast_to(jnp.log(jnp.arange(1, shape[1] + 1,
                                               dtype=jnp.float32)),
                            shape).astype(dtype)


def _dt_bias_init(key, shape, dtype, lo=1e-3, hi=1e-1):
    """The inverse softplus of a log-uniform ``delta`` in ``[lo, hi]``."""
    delta = jnp.exp(jax.random.uniform(key, shape, jnp.float32,
                                       math.log(lo), math.log(hi)))
    return (delta + jnp.log(-jnp.expm1(-delta))).astype(dtype)


def _taps_init(key, shape, dtype):
    """U(-1/2, 1/2): a depthwise convolution's default at four taps, taps
    and bias alike."""
    return jax.random.uniform(key, shape, jnp.float32, -0.5,
                              0.5).astype(dtype)


def _biased(cfg, name, width):
    return nn.Dense(width, use_bias=True, dtype=cfg.dtype,
                    param_dtype=cfg.param_dtype, kernel_init=blocks.init(),
                    name=name)


def _slot_tables(cfg, paging):
    """A paged call's table in its three parts: ``(the sequence's blocks
    [B, .], the slot's ring [B, ring], the slot's state row [B])``."""
    tables = paging["block_tables"]
    ring = cfg.paged_ring_blocks_for(cfg.paged_block_size)
    return tables[:, :-ring - 1], tables[:, -ring - 1:-1], tables[:, -1]


class Mamba1Mixer(nn.Module):
    """``u [B, T, d] -> (its term [B, T, d], pools)``: the Mamba-1 mixer of
    a whole sequence from zeros (plain call), of a whole prompt or a prefill
    chunk from the slot's stored state, and of a decode step (``T = 1``) on
    the pool in place. ``index``: the layer's place among the Mamba layers;
    ``work``: ``mamba1_scan.busy_rows`` of this step, or None. With
    ``memory`` it leaves ``pools["memory"] [B, T, C]`` float32, the scan's
    output with the ``D`` term, before the gate."""

    config: Phi4FlashConfig
    memory: bool = False

    @nn.compact
    def __call__(self, u, paging=None, pools=None, index=0, work=None):
        from deepspeed_tpu.ops.attention import record_dispatch

        cfg = self.config
        b, t, d = u.shape
        f32 = jnp.float32
        c, n, rank = cfg.mamba_inner, cfg.mamba_d_state, cfg.dt_rank
        param = self.param
        w_in = param("in_proj", blocks.init(), (d, 2 * c), cfg.param_dtype)
        taps = param("conv", _taps_init, (c, cfg.mamba_d_conv),
                     cfg.param_dtype)
        conv_bias = param("conv_bias", _taps_init, (c,), cfg.param_dtype)
        w_x = param("x_proj", blocks.init(), (c, rank + 2 * n),
                    cfg.param_dtype)
        w_dt = param("dt_proj", blocks.init(rank ** -0.5), (rank, c),
                     cfg.param_dtype)
        dt_bias = param("dt_bias", _dt_bias_init, (c,), cfg.param_dtype)
        a_log = param("A_log", _a_log_init, (c, n), cfg.param_dtype)
        skip = param("D", nn.initializers.ones, (c,), cfg.param_dtype)
        # [x | z], kept float32: x feeds a recurrence that hundreds of
        # positions compound
        xz = jnp.dot(u, w_in.astype(cfg.dtype), preferred_element_type=f32)
        x, z = xz[..., :c], xz[..., c:]
        serving = cfg.serving
        if serving:
            rows = _slot_tables(cfg, paging)[2]
            fresh = paging["lengths"] == 0
            num_valid = paging["num_valid"]
            held = pools["ssm_conv_pool"][index, rows]
            conv_state = jnp.where(fresh[:, None], jnp.zeros_like(held),
                                   held).reshape(b, -1, c)
        else:
            num_valid = jnp.full((b,), t, jnp.int32)
            conv_state = jnp.zeros((b, cfg.mamba_d_conv - 1, c), f32)
        with jax.named_scope("ssm._conv"):
            conv, conv_state = blocks.causal_conv(x, taps, conv_state,
                                                  num_valid)
            x = nn.silu(conv + conv_bias.astype(f32))
        dbc = jnp.dot(x.astype(cfg.dtype), w_x.astype(cfg.dtype),
                      preferred_element_type=f32)
        dt, bm, cm = (dbc[..., :rank], dbc[..., rank:rank + n],
                      dbc[..., rank + n:])
        delta = jax.nn.softplus(
            jnp.dot(dt.astype(cfg.dtype), w_dt.astype(cfg.dtype),
                    preferred_element_type=f32) + dt_bias.astype(f32))
        # a position past the row's last real one leaves the state as it is
        delta = jnp.where(jnp.arange(t)[None, :, None]
                          < num_valid[:, None, None], delta, 0.0)
        rate = mamba1_scan.rate_lanes(a_log)
        if serving and t == 1 and not paging.get("prefill"):
            record_dispatch("phi4_ssm_decode")
            y, state_pool = mamba1_scan.mamba1_state_update(
                pools["ssm_state_pool"], index, rows, delta[:, 0], x[:, 0],
                fresh, rate, bm[:, 0], cm[:, 0], work)
            y = y[:, None]
        else:
            if serving:
                record_dispatch("phi4_ssm_prefill_chunk")
                held = pools["ssm_state_pool"][index, rows]
                state = jnp.where(fresh[:, None, None, None],
                                  jnp.zeros_like(held), held)
            else:
                state = jnp.zeros((b, *mamba1_scan.pool_row_shape(c, n)),
                                  f32)
            y, state = mamba1_scan.mamba1_chunk_scan(x, delta, rate, bm, cm,
                                                     state)
            if serving:
                pool = pools["ssm_state_pool"]
                state_pool = pool.at[index, rows].set(state.astype(pool.dtype))
        if serving:
            pool = pools["ssm_conv_pool"]
            pools = {**pools, "ssm_state_pool": state_pool,
                     "ssm_conv_pool": pool.at[index, rows].set(
                         conv_state.reshape(b, -1).astype(pool.dtype))}
        y = y + skip.astype(f32) * x
        if self.memory:
            pools = {**pools, "memory": y}
        gated = (y * nn.silu(z)).astype(cfg.dtype)
        return blocks.dense(cfg, "out_proj", d)(gated), pools


class GatedMemoryUnit(nn.Module):
    """``(m * silu(u W_1)) W_2``: the memory (``pools["memory"]``, at the
    positions of ``u``: a call the shell has cut carries the row at
    ``pools["row_at"]``) gated by this layer's own stream."""

    config: Phi4FlashConfig

    @nn.compact
    def __call__(self, u, pools):
        cfg = self.config
        m = pools["memory"]
        if m.shape[1] != u.shape[1]:
            m = blocks.last_rows(m, pools["row_at"])
        gate = blocks.dense(cfg, "in_proj", cfg.mamba_inner)(u)
        gated = (m * nn.silu(gate.astype(jnp.float32))).astype(cfg.dtype)
        return blocks.dense(cfg, "out_proj", cfg.hidden_size)(gated), pools


def paired(q):
    """Query heads ``[.., H, dk]`` in the order the attention paths group
    them: the ``q1`` of a KV pair's two query pairs, then their ``q2``, so
    that grouped-query attention (head ``p`` against KV head ``p // (H /
    KV)``) scores ``q1`` against ``k1`` and ``q2`` against ``k2``."""
    *lead, heads, dk = q.shape
    return q.reshape(*lead, heads // 4, 2, 2, dk).swapaxes(-2, -3).reshape(
        *lead, heads, dk)


class DiffAttention(nn.Module):
    """Differential attention of one layer: ``kind`` ``"window"`` (the
    slot's ring), ``"full"`` (writes THE cache) or ``"cross"`` (reads it: a
    query projection and an output projection, nothing else). ``layer``: the
    layer's index (``lambda_init``); ``index``: its place in its kind's
    pools."""

    config: Phi4FlashConfig
    kind: str = "full"
    layer: int = 0

    @nn.compact
    def __call__(self, x, paging=None, pools=None, index=0, work=None):
        cfg = self.config
        b, t, _ = x.shape
        heads, kv, dh = (cfg.num_attention_heads, cfg.num_key_value_heads,
                         cfg.head_dim)
        if self.kind == "cross":
            q = _biased(cfg, "q_proj", heads * dh)(x)
            k = v = None
        else:
            qkv = _biased(cfg, "qkv_proj", (heads + 2 * kv) * dh)(x)
            q = qkv[..., :heads * dh]
            k = qkv[..., heads * dh:(heads + kv) * dh].reshape(b, t, kv, dh)
            v = qkv[..., (heads + kv) * dh:].reshape(b, t, kv, dh)
        q = paired(q.reshape(b, t, heads, dh))
        lam = [self.param(name, blocks.init(0.1), (dh,), jnp.float32)
               for name in ("lambda_q1", "lambda_k1", "lambda_q2",
                            "lambda_k2")]
        init = cfg.lambda_init(self.layer)
        lam = (jnp.exp(jnp.sum(lam[0] * lam[1]))
               - jnp.exp(jnp.sum(lam[2] * lam[3])) + init)
        if not cfg.serving:
            if self.kind == "cross":
                k, v = pools["shared_kv"]
            elif self.kind == "full":
                pools = {**pools, "shared_kv": (k, v)}
            window = cfg.sliding_window if self.kind == "window" else 0
            y = blocks.causal_gqa(q, k, blocks.value_groups(v, VALUE_GROUP),
                                  window)
        else:
            y, pools = self._paged(q, k, v, paging, pools, index, work)
        # [B, T, KV pairs, (q1 | q2), query pairs, 2 dk]: the difference,
        # the norm over a pair's values, the pairs side by side
        y = y.astype(jnp.float32).reshape(b, t, heads // 4, 2, 2, 2 * dh)
        o = y[:, :, :, 0] - lam * y[:, :, :, 1]
        o = blocks.RMSNorm(1e-5, jnp.float32, name="subln")(o) * (1.0 - init)
        out = _biased(cfg, "o_proj", cfg.hidden_size)(
            o.reshape(b, t, heads * dh).astype(cfg.dtype))
        return out, pools

    def _paged(self, q, k, v, paging, pools, index, work):
        """Write this call's keys and values where the kind of layer keeps
        them, and attend: a window layer in the slot's ring, the full layer
        through the sequence's table, a cross layer over what the full
        layer has written (this call's rows among them)."""
        cfg = self.config
        t = q.shape[1]
        seq, ring, _ = _slot_tables(cfg, paging)
        key_tile = min(CHUNK_KEY_TILE,
                       seq.shape[-1] * cfg.paged_block_size // 2)
        if self.kind == "window":
            pos = blocks.call_positions(cfg, paging, t)
            y, k_pool, v_pool = blocks.ring_gqa(
                q, k, v, pos, paging, ring, pools["window_key_pool"],
                pools["window_value_pool"], index, "phi4_window", None,
                work, window=cfg.sliding_window, value_group=VALUE_GROUP)
            return y, {**pools, "window_key_pool": k_pool,
                       "window_value_pool": v_pool}
        k_pool, v_pool = pools["global_key_pool"], pools["global_value_pool"]
        if self.kind == "full":
            pos = blocks.call_positions(cfg, paging, t)
            y, k_pool, v_pool = blocks.paged_gqa(
                q, k, v, pos, paging, seq, k_pool, v_pool, 0, "phi4_global",
                work=work, key_tile=key_tile, value_group=VALUE_GROUP)
            return y, {**pools, "global_key_pool": k_pool,
                       "global_value_pool": v_pool}
        return cross_attend(cfg, q, paging, seq, k_pool, v_pool, work,
                            pools.get("row_at")), pools


def cross_attend(cfg, q, paging, table, k_pool, v_pool, work, row_at=None):
    """A cross layer's one query row a sequence (``q [B, 1, H, dk]``) over
    what the full layer keeps in the one global pool through ``table``: a
    decode step's row at position ``lengths``, or, in a call the shell has
    cut, the row at ``lengths + row_at`` over the call's own rows too. On a
    TPU a decode step runs the paged kernel over the work list ``work``
    (the full layer's own: the same table and lengths); a cut call, and
    every step where no TPU is, takes ``blocks.cached_gqa``."""
    from deepspeed_tpu.ops.attention import (record_dispatch,
                                             use_decode_kernel)
    from deepspeed_tpu.ops.hybrid_decode_attention import (
        decode_attention_hybrid)

    kv, dh = cfg.num_key_value_heads, cfg.head_dim
    lengths = paging["lengths"]
    if row_at is None and use_decode_kernel():
        record_dispatch("phi4_cross_decode_kernel")
        with jax.named_scope("attn._hybrid_kv_attend"):
            return decode_attention_hybrid(
                q, k_pool, v_pool, table, lengths, 0, kv_heads=kv, work=work,
                value_group=VALUE_GROUP)
    if row_at is not None:
        lengths = lengths + row_at
    key_tile = min(CHUNK_KEY_TILE,
                   table.shape[-1] * cfg.paged_block_size // 2)
    return blocks.cached_gqa(q, lengths[:, None], paging, table,
                             (k_pool, v_pool, 0), (kv, dh, dh), "phi4_cross",
                             None, key_tile, VALUE_GROUP)


class Phi4FlashForCausalLM(blocks.PagedDecoder):
    """``blocks.PagedDecoder`` over the layer kinds, LayerNorm, the head the
    embedding. No layer is sparse: the four counters are zeros; behind them
    the rows the cross-decoder ran and the tokens the self-decoder ran."""

    config: Phi4FlashConfig
    tied = True
    serve_routed = False
    eps_field = "layer_norm_eps"
    norm_class = blocks.LayerNorm
    carries = True
    serve_counters = dropless.COUNTERS + ("cross_rows", "self_tokens")
    pool_dtypes = {"ssm_state_pool": jnp.float32}
    # the engine reads this leaf's layout to choose ``paging["lookup"]``
    lookup_table = "embed_tokens"

    def lookup(self, table, ids, paging):
        return embed_lookup(table, ids, (paging or {}).get("lookup", "rows"))

    def rows_from(self, i: int) -> bool:
        return i == self.config.half + 2

    def pool_shapes(self, num_blocks, block_size):
        """ONE layer of global keys and of values (the engine's
        ``num_blocks``), the window layers' rings (the garbage block and a
        ring a slot), and the Mamba layers' two state pools (row 0 for idle
        rows, then a row a slot)."""
        cfg = self.config
        lanes = cfg.num_key_value_heads * cfg.head_dim
        slots = cfg.paged_state_slots
        ring = cfg.paged_ring_blocks_for(block_size)
        window = (len(cfg.layers_of("window")), 1 + slots * ring, block_size,
                  lanes)
        mamba, c = len(cfg.layers_of("mamba")), cfg.mamba_inner
        return {
            "global_key_pool": (1, num_blocks, block_size, lanes),
            "global_value_pool": (1, num_blocks, block_size, lanes),
            "window_key_pool": window, "window_value_pool": window,
            "ssm_state_pool": (mamba, 1 + slots, *mamba1_scan.pool_row_shape(
                c, cfg.mamba_d_state)),
            "ssm_conv_pool": (mamba, 1 + slots, (cfg.mamba_d_conv - 1) * c)}

    def more_counters(self, routed, valid, pools):
        ran = pools.get("self_valid", valid)
        return jnp.stack([jnp.sum(valid), jnp.sum(ran)]).astype(jnp.int32)

    def step_work(self, paging):
        """The grids of this step's kernels, each the same for every layer
        of its kind: the shared cache's and the rings' follow the lengths,
        the state update's the busy rows."""
        from deepspeed_tpu.ops.hybrid_decode_attention import (
            hybrid_plan, hybrid_work_list)

        cfg = self.config
        seq, ring, rows = _slot_tables(cfg, paging)
        lanes = cfg.num_key_value_heads * cfg.head_dim

        def work(blocks_):
            plan = hybrid_plan(cfg.paged_block_size, lanes, lanes, blocks_)
            return hybrid_work_list(paging["lengths"], seq, plan)

        return {"global": work(seq.shape[-1]), "window": work(ring.shape[-1]),
                "mamba": mamba1_scan.busy_rows(rows)}

    def mixer(self, i, u, paging, pools, work):
        cfg = self.config
        kind = cfg.kind(i)
        work = work or {}
        if kind == "mamba":
            return Mamba1Mixer(cfg, i == cfg.half, name=f"layers_{i}_mamba")(
                u, paging, pools, cfg.layers_of(kind).index(i),
                work.get("mamba"))
        if kind == "gmu":
            return GatedMemoryUnit(cfg, name=f"layers_{i}_gmu")(u, pools)
        place = cfg.layers_of(kind).index(i) if kind == "window" else 0
        return DiffAttention(cfg, kind, i, name=f"layers_{i}_attn")(
            u, paging, pools, place,
            work.get("window" if kind == "window" else "global"))
