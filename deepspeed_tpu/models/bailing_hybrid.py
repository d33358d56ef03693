"""The Ling-3.0 family (``model_type: bailing_hybrid``): channel-gated
delta-rule layers (KDA) beside one latent-attention layer a period, over a
dense or a sparse FFN with group-limited routing and one shared expert.

Layer ``i``, pre-norm, RMSNorm with a learned weight, no bias anywhere:
``h = x + Mixer_i(norm x)``, ``out = h + FFN_i(norm h)``; a final norm; an
untied head.

- KDA mixer (``(i + 1) % layer_group_size != 0``), ``H`` heads of key and
  value width ``head_dim``: ``q, k, v = silu(conv(x W_q)), silu(conv(x
  W_k)), silu(conv(x W_v))``, the convolution causal, depthwise,
  ``short_conv_kernel_size`` taps (zeros before the start); ``q <- q /
  |q|_2 * head_dim^-0.5``, ``k <- k / |k|_2`` a head (``use_qk_norm``). A
  decay a KEY CHANNEL: ``g_t = kda_lower_bound * sigmoid(exp(A_log_h) *
  (x_t W_f + dt_bias))`` (``kda_safe_gate``: ``kda_lower_bound < g < 0``),
  ``alpha_t = exp(g_t)``; ``beta_t = sigmoid(x_t W_b)`` a head. A head's
  state ``S [head_dim (key), head_dim (value)]``, zero at a sequence's
  start: ``S' = Diag(alpha_t) S_{t-1}``; ``S_t = S' + beta_t k_t (v_t -
  S'^T k_t)^T``; ``o_t = S_t^T q_t``. ``y = (sigmoid(x W_g) *
  RMSNorm_head(o)) W_o``, the norm over a head's values with one learned
  weight of ``head_dim``.
- Latent mixer (every ``layer_group_size``-th layer):
  ``models/deepseek_v2.py``'s multi-head latent attention (no query
  compression, interleaved pairs rotated at ``rope_theta`` with no scaling,
  scores ``* (nope + rope) ** -0.5``), and a gate a HEAD on its output
  before ``W_o``: ``o_h <- sigmoid(x W_a)_h * o_h``
  (``gated_attention_proj_granularity_type: head_wise``).
- FFN: SwiGLU for ``i < first_k_dense_replace``; else sigmoid scores over
  all ``num_experts``, ``num_experts_per_tok`` chosen inside the
  ``topk_group`` best of ``n_group`` groups by score + a selection bias (a
  group's score the sum of its two largest), the chosen scores normalised
  and scaled by ``routed_scaling_factor`` (``moe/dropless.py``), plus one
  shared expert; where a layer's entry of ``expert_swiglu_limit_list`` /
  ``share_expert_swiglu_limit_list`` is ``L > 0`` the experts' / the shared
  expert's SwiGLU clamps ``gate <- min(gate, L)``, ``up <- clip(up, -L,
  L)`` first.

Four forms above are READINGS of key names by the family's and its sources'
convention (Kimi Linear's KDA, arXiv:2510.26692; the gated-attention
paper's head-wise variant; DeepSeek-V3's ``noaux_tc``; gpt-oss's clamp):
the decay's bounded form, the head-wise gate's place, the group score as a
sum of two, the clamp's two sides; and ``use_qk_norm`` is read as the KDA
layers' L2 norm of ``q`` and ``k`` (the latent layers keep only their
latent's norm). The benchmark's configuration file states them under
``assumed``, and its reference computes the same.

The norms, SwiGLU, the causal convolution, the sparse FFN and the decoder
shell are ``models/blocks.py``'s; the latent attention's two forms and its
pool row are ``models/deepseek_v2.py``'s (:class:`GatedLatentAttention`
subclasses its module and calls its functions: that file is not edited).
This file holds the config, the KDA mixer, the gate on the latent layer and
the pools.

SERVING. ``for_paged_decode`` gives the module BOTH kinds of per-sequence
state the engine knows (``serving/engine.py``): ``latent_pool [latent
layers, blocks, block_size, lanes]``, one row ``[c | k_pe]`` a token
through the sequence's block table (``paged_row_kind``), and two pools of
per-slot state (``paged_slot_state_for``: the table's LAST entry is the
slot's row): ``kda_state_pool [KDA layers, 1 + slots, H, K, V]`` in
FLOAT32 whatever ``dtype`` is, and ``kda_conv_pool [KDA layers, 1 + slots,
(taps - 1) x 3 H K]``, the last rows of the three convolutions' inputs side
by side in one pool row (``dtype``: they are a bfloat16 projection's
outputs as they were). Row 0 of both is what idle rows write. The
recurrence has two forms of one arithmetic: the chunked form for ``T > 1``
(``ops/kda_chunk.py``: a chunk starts from the slot's stored state and
writes it back) and the in-place update for a decode step
(``ops/kda_state_update.py``: a Pallas kernel on a TPU, the pool aliased).

THE STATE'S PRECISION is float32 in the pool and in both forms. The delta
rule's correction ``v - S'^T k`` is a difference of near-equal terms once a
key has been written, so a state rounded to bfloat16 every step loses it:
the benchmark's check holds the served state of a finished request to the
reference's recurrence, and its ``bfloat16-state`` control (the pool's
values through bfloat16 every step) is what it reads as not correct
(PERF.md, PR 57, has both readings).
"""

import dataclasses
import functools
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models import blocks, deepseek_v2
from deepspeed_tpu.moe import dropless
from deepspeed_tpu.ops import kda_chunk, kda_state_update

KDA, LATENT = "kda", "latent"


@dataclasses.dataclass(frozen=True)
class BailingHybridConfig(blocks.ServedConfig):
    vocab_size: int = 157184
    hidden_size: int = 2560
    num_hidden_layers: int = 42
    num_attention_heads: int = 32
    head_dim: int = 128                   # a KDA head's key and value width
    layer_group_size: int = 6             # every 6th layer is latent
    short_conv_kernel_size: int = 4
    kda_lower_bound: float = -5.0
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 128
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    intermediate_size: int = 6144
    first_k_dense_replace: int = 2
    moe_intermediate_size: int = 768
    moe_shared_expert_intermediate_size: int = 768
    num_experts: int = 512                # the router's width: ALL experts
    num_experts_per_tok: int = 8
    n_group: int = 8
    topk_group: int = 4
    num_shared_experts: int = 1
    routed_scaling_factor: float = 2.5
    # a layer's clamp on its experts' / its shared expert's SwiGLU (0: none)
    expert_swiglu_limit_list: Tuple[float, ...] = ()
    share_expert_swiglu_limit_list: Tuple[float, ...] = ()
    # the experts held here: rank ep_rank of ep_size equal contiguous shares
    # (at ep_size == n_group a share IS a routing group)
    ep_rank: int = 0
    ep_size: int = 1
    rms_norm_eps: float = 1e-6
    rope_theta: float = 6e6
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
    paged_state_slots: int = 0
    paged_return_routed: bool = False

    def __post_init__(self):
        n = self.num_hidden_layers
        for name in ("expert_swiglu_limit_list",
                     "share_expert_swiglu_limit_list"):
            if len(getattr(self, name)) not in (0, n):
                raise ValueError(f"{name} needs one entry a layer ({n}) or "
                                 f"none, got {getattr(self, name)}")
        if self.num_experts % self.n_group or not (
                1 <= self.topk_group <= self.n_group):
            raise ValueError(
                f"{self.num_experts} experts in {self.n_group} groups, "
                f"{self.topk_group} kept")
        if self.qk_rope_head_dim % 2:
            raise ValueError(f"qk_rope_head_dim {self.qk_rope_head_dim} "
                             "rotates pairs")
        dropless.held_range(self.num_experts, self.ep_rank, self.ep_size)

    # the contract's (blocks.ServedConfig): the slots' keyword, why
    # kv_dtype is refused, the layers that are sparse and their routing
    slot_knob = "state_slots"
    unquantized = "delta-rule state and latent rows have no quantized pool"
    # what deepseek_v2's latent attention reads of a config beside the
    # fields above: no frequency scaling (``rope_scaling`` null)
    rope_scaling = None

    def sparse(self, i: int) -> bool:
        return i >= self.first_k_dense_replace

    def _limit(self, name: str, i: int) -> float:
        limits = getattr(self, name)
        return float(limits[i]) if limits else 0.0

    def sparse_ffn_at(self, i: int) -> dict:
        """:class:`blocks.SparseFFN`'s arguments for layer ``i`` (the
        clamps are a layer's own)."""
        return dict(
            experts=self.num_experts, top_k=self.num_experts_per_tok,
            width=self.moe_intermediate_size, scoring="sigmoid",
            renormalize=True, scale=self.routed_scaling_factor,
            bias_std=self.selection_bias_std, n_group=self.n_group,
            topk_group=self.topk_group,
            limit=self._limit("expert_swiglu_limit_list", i),
            shared_limit=self._limit("share_expert_swiglu_limit_list", i),
            shared_width=(self.num_shared_experts
                          * self.moe_shared_expert_intermediate_size),
            ep_rank=self.ep_rank, ep_size=self.ep_size, dtype=self.dtype,
            param_dtype=self.param_dtype)

    def kind(self, i: int) -> str:
        return LATENT if (i + 1) % self.layer_group_size == 0 else KDA

    def layers_of(self, kind: str):
        """Indices of the layers of one kind, in order: a layer's place in
        its kind's pools is its place here."""
        return [i for i in range(self.num_hidden_layers)
                if self.kind(i) == kind]

    # ---- what deepseek_v2's latent attention reads of a config
    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def latent_row(self) -> int:
        """Values a token keeps a latent layer: ``[c | k_pe]``."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self) -> int:
        """Lanes of a pool row: the row in whole 128-lane registers."""
        return -(-self.latent_row // 128) * 128

    @property
    def softmax_scale(self) -> float:
        return self.qk_head_dim ** -0.5

    # ---- the two kinds of per-sequence state
    @property
    def kda_inner(self) -> int:
        return self.num_attention_heads * self.head_dim

    @property
    def conv_width(self) -> int:
        """What the convolutions mix: ``q | k | v``."""
        return 3 * self.kda_inner

    def kv_bytes_per_token(self) -> dict:
        """Bytes one token keeps: the latent layers' rows as they are
        COUNTED (576 values a layer), whatever lanes the pool pads to."""
        return {"latent": len(self.layers_of(LATENT)) * self.latent_row
                * jnp.dtype(self.dtype).itemsize}

    def state_bytes_per_slot(self) -> dict:
        """Bytes a decode slot's state takes, all KDA layers, by kind: the
        float32 matrix a head, and the convolutions' last rows."""
        layers = len(self.layers_of(KDA))
        return {"state": layers * self.kda_inner * self.head_dim * 4,
                "conv": layers * (self.short_conv_kernel_size - 1)
                * self.conv_width * jnp.dtype(self.dtype).itemsize}

    def paged_row_kind(self) -> dict:
        """What a row of this model's block pool is (the engine's seam)."""
        return {"kind": "latent",
                "what": f"block pool keeps one latent row a token "
                        f"({self.latent_row} values shared by all "
                        f"{self.num_attention_heads} heads, no keys and "
                        "values by heads)"}

    def paged_slot_state_for(self, block_size: int):
        """What a decode slot keeps beside its block table (the engine's
        per-slot seam): one entry of the table, the slot's row of the two
        state pools. None without KDA layers."""
        if not self.layers_of(KDA):
            return None
        return {"entries": 1, "knob": self.slot_knob,
                "what": "delta-rule layers keep a state of fixed size a "
                        "decode slot (a float32 matrix a head and the "
                        "convolutions' last rows), written in place every "
                        "step"}

    def kv_live_bytes(self, live) -> dict:
        """Bytes of per-sequence state a decode step reads, by kind, for
        busy rows of the lengths ``live``: the latent layers' row of every
        token, the KDA layers' state and convolution rows a busy slot."""
        return {"latent": int(live.sum())
                * self.kv_bytes_per_token()["latent"],
                **{kind: len(live) * nbytes for kind, nbytes
                   in self.state_bytes_per_slot().items()}}

    @staticmethod
    def tiny(**kw):
        """The CPU tests' size: every mechanism, no published width."""
        base = dict(vocab_size=128, hidden_size=64, num_hidden_layers=6,
                    num_attention_heads=4, head_dim=16, layer_group_size=3,
                    kv_lora_rank=32, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, intermediate_size=128,
                    first_k_dense_replace=1, moe_intermediate_size=32,
                    moe_shared_expert_intermediate_size=32, num_experts=32,
                    num_experts_per_tok=4, n_group=4, topk_group=2,
                    expert_swiglu_limit_list=(0, 0, 0, 0, 0.5, 0.5),
                    share_expert_swiglu_limit_list=(0, 0, 0, 0, 0, 0.25),
                    max_position_embeddings=4096, selection_bias_std=0.01)
        base.update(kw)
        return BailingHybridConfig(**base)


# ---------------------------------------------------------------------------
# the KDA gate's own initialisers: at N(0, 0.02) every channel would sit at
# half the lower bound (alpha = e^-2.5: a state forgotten within two
# positions) and nothing would tell a state carried from a state lost

def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 0.5,
                                      1.0)).astype(dtype)


def _dt_bias_init(key, shape, dtype):
    """U(-6, -2): at a zero projection a channel decays by ``exp(-5
    sigmoid(rate x bias))``, ``rate`` in [0.5, 1]: 0.26 to 0.99 a position,
    and a projection of order 1 moves it between the bound and 1."""
    return jax.random.uniform(key, shape, jnp.float32, -6.0, -2.0).astype(
        dtype)


def _taps_init(key, shape, dtype):
    """U(-1/2, 1/2): a depthwise convolution's default at four taps."""
    return jax.random.uniform(key, shape, jnp.float32, -0.5,
                              0.5).astype(dtype)


def l2_normed(x, eps: float = 1e-6):
    """``x / |x|_2`` over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def kda_gate(cfg, f, a_log, dt_bias):
    """The log decay a key channel, ``[B, T, H, K]`` float32 in
    ``(kda_lower_bound, 0)``, of the projection ``f [B, T, H K]``."""
    heads, width = cfg.num_attention_heads, cfg.head_dim
    z = (f.astype(jnp.float32) + dt_bias.astype(jnp.float32)).reshape(
        *f.shape[:2], heads, width)
    rate = jnp.exp(a_log.astype(jnp.float32))[:, None]
    return cfg.kda_lower_bound * jax.nn.sigmoid(rate * z)


def state_in(pool, index, rows, fresh):
    """The state a paged call's rows start from, of layer ``index`` of one
    of the state pools: what their slots hold (``rows [B]`` into the pool),
    and zeros for a sequence at length 0 (``fresh [B]``), whatever its
    slot's last tenant left."""
    held = pool[index, rows]
    return jnp.where(fresh.reshape(-1, *(1,) * (held.ndim - 1)),
                     jnp.zeros_like(held), held)


def rows_written(pool, index, rows, values):
    """``pool`` with ``values [B, ...]`` in rows ``rows [B]`` of layer
    ``index`` (``pool.at[index, rows].set(values)``; where two rows of the
    call name one pool row, as idle rows name row 0, the first's values
    land), written as ONE slab of the layer and not as a scatter: the
    chip's compiler runs a scatter whose rows may repeat a row at a time
    (128 dependent updates of 74 kB a layer a decode step were a fifth of
    the cell's device time: PERF.md, PR 57), where the layer's slab gathered
    from the call's rows is 19 MB of traffic."""
    layer = pool[index]
    named = rows[None, :] == jnp.arange(layer.shape[0])[:, None]   # [R, B]
    taken = jnp.take(values.astype(pool.dtype), jnp.argmax(named, axis=1),
                     axis=0)
    fresh = jnp.where(jnp.any(named, axis=1).reshape(
        -1, *(1,) * (layer.ndim - 1)), taken, layer)
    return pool.at[index].set(fresh)


class KdaMixer(nn.Module):
    """``x [B, T, d] -> (its term [B, T, d], pools)``: the KDA mixer of a
    whole sequence from zeros (plain call), of a whole prompt or a prefill
    chunk from the slot's stored state, and of a decode step (``T = 1``) on
    the pool in place. ``index``: the layer's place among the KDA layers
    (its row of the state pools); ``work``: ``kda_state_update.busy_rows``
    of this step, or None."""

    config: BailingHybridConfig

    @nn.compact
    def __call__(self, x, paging=None, pools=None, index=0, work=None):
        from deepspeed_tpu.ops.attention import (record_dispatch,
                                                 use_decode_kernel)

        cfg = self.config
        b, t, d = x.shape
        f32 = jnp.float32
        heads, width, inner = (cfg.num_attention_heads, cfg.head_dim,
                               cfg.kda_inner)
        proj = functools.partial(blocks.dense, cfg)
        with jax.named_scope("kda._project"):
            qkv = jnp.concatenate([proj(name, inner)(x) for name in
                                   ("q_proj", "k_proj", "v_proj")], axis=-1)
            w_f = self.param("f_proj", blocks.init(), (d, inner),
                             cfg.param_dtype)
            w_b = self.param("b_proj", blocks.init(), (d, heads),
                             cfg.param_dtype)
            # the decay and the write strength stay float32: thousands of
            # positions compound them
            f = jnp.dot(x, w_f.astype(cfg.dtype), preferred_element_type=f32)
            beta = jax.nn.sigmoid(jnp.dot(x, w_b.astype(cfg.dtype),
                                          preferred_element_type=f32))
            gate = proj("g_proj", inner)(x)
        taps = self.param("conv", _taps_init,
                          (cfg.conv_width, cfg.short_conv_kernel_size),
                          cfg.param_dtype)
        a_log = self.param("A_log", _a_log_init, (heads,), cfg.param_dtype)
        dt_bias = self.param("dt_bias", _dt_bias_init, (inner,),
                             cfg.param_dtype)
        serving = cfg.serving
        if serving:
            rows = paging["block_tables"][:, -1]
            fresh = paging["lengths"] == 0
            num_valid = paging["num_valid"]
            conv_state = state_in(pools["kda_conv_pool"], index, rows,
                                  fresh).reshape(b, -1, cfg.conv_width)
        else:
            num_valid = jnp.full((b,), t, jnp.int32)
            conv_state = jnp.zeros(
                (b, cfg.short_conv_kernel_size - 1, cfg.conv_width),
                cfg.dtype)
        with jax.named_scope("kda._conv"):
            conv, conv_state = blocks.causal_conv(qkv, taps, conv_state,
                                                  num_valid)
            q, k, v = (u.reshape(b, t, heads, width) for u in
                       jnp.split(nn.silu(conv), 3, axis=-1))
            q, k = l2_normed(q) * width ** -0.5, l2_normed(k)
        g = kda_gate(cfg, f, a_log, dt_bias)
        # a position past the row's last real one leaves the state as it is
        real = jnp.arange(t)[None, :, None] < num_valid[:, None, None]
        g = jnp.where(real[..., None], g, 0.0)
        beta = jnp.where(real, beta, 0.0)
        # (kept only by a caller that asks for ``intermediates``: what the
        # recurrence below is given, for a check of the recurrence alone)
        self.sow("intermediates", "recurrence_inputs", (q, k, v, g, beta))
        if serving and t == 1 and not paging.get("prefill"):
            # a decode step: the pool in place
            kernel = use_decode_kernel() and kda_state_update.kernel_serves(
                heads, width, width)
            record_dispatch("kda_decode_" + ("kernel" if kernel else "xla"))
            alpha = jnp.where(fresh[:, None, None], 0.0, jnp.exp(g[:, 0]))
            update = (functools.partial(kda_state_update.state_update_kernel,
                                        work=work) if kernel
                      else kda_state_update.state_update_xla)
            with jax.named_scope("kda._step"):
                o, state_pool = update(
                    pools["kda_state_pool"], index, rows, alpha, k[:, 0],
                    v[:, 0], q[:, 0], beta[:, 0])
            o = o[:, None]
        else:
            if serving:
                record_dispatch("kda_prefill_chunk")
                state = state_in(pools["kda_state_pool"], index, rows, fresh)
            else:
                state = jnp.zeros((b, heads, width, width), f32)
            with jax.named_scope("kda._chunk"):
                o, state = kda_chunk.kda_chunk(q, k, v, g, beta, state)
            if serving:
                pool = pools["kda_state_pool"]
                state_pool = pool.at[index, rows].set(
                    state.astype(pool.dtype))
        if serving:
            pools = {**pools, "kda_state_pool": state_pool,
                     "kda_conv_pool": rows_written(
                         pools["kda_conv_pool"], index, rows,
                         conv_state.reshape(b, -1))}
        # the norm a head, then the gate
        normed = blocks.RMSNorm(cfg.rms_norm_eps, f32, name="o_norm")(o)
        gated = (jax.nn.sigmoid(gate.astype(f32)).reshape(o.shape)
                 * normed).reshape(b, t, inner)
        return proj("o_proj", d)(gated.astype(cfg.dtype)), pools


class GatedLatentAttention(deepseek_v2.LatentAttention):
    """``models/deepseek_v2.py``'s latent attention (its projections, its
    rotation, its two forms over the latent pool, none of it restated) with
    a gate a head on the attention's output before ``o_proj``."""

    config: BailingHybridConfig

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
        w_kvb = self.param("kv_b_proj", blocks.init(),
                           (rank, heads * (nope + dv)),
                           cfg.param_dtype).astype(cfg.dtype).reshape(
                               rank, heads, nope + dv)
        with jax.named_scope("mla._head_gate"):
            gate = jax.nn.sigmoid(proj("gate_proj", heads)(x).astype(
                jnp.float32))
        pos = blocks.call_positions(cfg, paging, t)
        if not cfg.serving:
            pos = jnp.broadcast_to(pos, (b, t))
        q_nope = q[..., :nope]
        q_pe = deepseek_v2.rotate_pairs(q[..., nope:], pos, cfg)
        k_pe = deepseek_v2.rotate_pairs(kva[..., rank:], pos, cfg)
        if not cfg.serving:
            y = self._decompressed(q_nope, q_pe, w_kvb, pos,
                                   jnp.full((b,), t, jnp.int32),
                                   self._own_rows(c, k_pe, t))
        else:
            y, pool = self._paged(q_nope, q_pe, c, k_pe, w_kvb, pos, paging,
                                  pool, index, work)
        y = (y.astype(jnp.float32) * gate[..., None]).astype(cfg.dtype)
        out = proj("o_proj", cfg.hidden_size)(y.reshape(b, t, heads * dv))
        return out, pool


def SparseExperts(config, layer: int, **kw):
    """The sparse FFN of layer ``layer`` of a config, by the name the
    benchmark's families build it under."""
    return blocks.SparseFFN(**config.sparse_ffn_at(layer), **kw)


class BailingHybridForCausalLM(blocks.PagedDecoder):
    """``blocks.PagedDecoder`` over the two kinds of mixer, an untied
    head."""

    config: BailingHybridConfig
    # the state is float32 whatever the model is served in
    pool_dtypes = {"kda_state_pool": jnp.float32}
    # behind the sparse layers' four: (token, sparse layer) pairs that chose
    # an expert of the GROUP held here, and all of them (half, where the
    # selection bias is balanced and ``topk_group`` is half of ``n_group``)
    serve_counters = dropless.COUNTERS + ("tokens_group_here",
                                          "tokens_routed")

    def more_counters(self, routed, valid, pools):
        cfg = self.config
        first, count = dropless.held_range(cfg.num_experts, cfg.ep_rank,
                                           cfg.ep_size)
        with jax.named_scope("moe._group_here"):
            here = sum(jnp.sum(valid & jnp.any(
                (chosen >= first) & (chosen < first + count), axis=-1),
                dtype=jnp.int32) for chosen in routed)
            return jnp.stack([jnp.asarray(here, jnp.int32), len(routed)
                              * jnp.sum(valid, dtype=jnp.int32)])

    def pool_shapes(self, num_blocks, block_size):
        """The latent layers' pool through the block table (the engine's
        ``num_blocks``), and the KDA layers' two state pools (row 0 for
        idle rows, then a row a slot)."""
        cfg = self.config
        shapes = {}
        latent, kda = (len(cfg.layers_of(k)) for k in (LATENT, KDA))
        if latent:
            shapes["latent_pool"] = (latent, num_blocks, block_size,
                                     cfg.latent_lanes)
        if kda:
            slots = 1 + cfg.paged_state_slots
            shapes["kda_state_pool"] = (kda, slots, cfg.num_attention_heads,
                                        cfg.head_dim, cfg.head_dim)
            shapes["kda_conv_pool"] = (
                kda, slots,
                (cfg.short_conv_kernel_size - 1) * cfg.conv_width)
        return shapes

    def step_work(self, paging):
        """The grids of this step's kernels, each the same for every layer
        of its kind: the latent attention's follows the lengths, the state
        update's the busy rows."""
        from deepspeed_tpu.ops.latent_decode_attention import latent_step_work

        cfg, tables = self.config, paging["block_tables"]
        return (latent_step_work(paging["lengths"], tables[:, :-1],
                                 cfg.paged_block_size, cfg.latent_lanes),
                kda_state_update.busy_rows(tables[:, -1]))

    def mixer(self, i, u, paging, pools, work):
        cfg = self.config
        kind = cfg.kind(i)
        place = cfg.layers_of(kind).index(i)
        latent_work, kda_work = work or (None, None)
        if kind == KDA:
            with jax.named_scope("kda"):
                return KdaMixer(cfg, name=f"layers_{i}_kda")(
                    u, paging, pools, place, kda_work)
        # the latent layer sees the sequence's blocks alone: the table's
        # last entry is the slot's state row
        seq = paging and {**paging,
                          "block_tables": paging["block_tables"][:, :-1]}
        a, pool = GatedLatentAttention(cfg, name=f"layers_{i}_attn")(
            u, seq, pools and pools["latent_pool"], place, latent_work)
        return a, pools and {**pools, "latent_pool": pool}
