"""The Granite-4.0-H family (``granitemoehybrid``, dense members): Mamba-2
layers beside a few grouped-query attention layers, every layer followed by
a SwiGLU, no positional encoding, four scalar multipliers.

``x_0 = embedding_multiplier * E[ids]``; layer ``i``, pre-norm, RMSNorm with
a learned weight, no bias in any projection: ``h = x + r * Mix_i(norm x)``,
``x' = h + r * MLP(norm h)`` (``r = residual_multiplier``); logits ``=
norm(x_L) E^T / logits_scaling`` (the head is the embedding, tied).

- ``layer_types[i] == "attention"``: grouped-query heads of ``hidden /
  heads``, NO rotation (``position_embedding_type: "nope"``), scores ``q
  k^T * attention_multiplier`` (not ``dk ** -0.5``).
- ``"mamba"`` (Mamba-2; inner width ``H x P``, state size ``N``, one
  group, ``K`` taps): ``[z | xBC | dt] = u W_in``; ``xBC <- silu(conv_K(xBC)
  + b)`` (depthwise, causal, zeros before the start); split ``x [H, P]``,
  ``B [N]``, ``C [N]`` (shared by the heads); ``delta_t = softplus(dt_t +
  dt_bias)``, ``a_t = exp(delta_t A)``, ``A = -exp(A_log)`` a scalar a
  head; a head's state ``S_t = a_t S_{t-1} + delta_t x_t B_t^T`` (``[P,
  N]``), ``y_t = S_t C_t + D x_t``; ``Mix(u) = RMSNorm_w(y * silu(z))
  W_out`` (the gate BEFORE the norm, the norm over all ``H x P``). All a
  sequence keeps of its past in such a layer is ``S`` and the last ``K - 1``
  rows of ``xBC``: a state of FIXED SIZE whatever its length, a matrix a
  head (at the published widths 64 x 64 x 128 values a layer, 1 MB in
  bfloat16).

The norms, SwiGLU, the attention arithmetic, the causal convolution and the
decoder shell (with the multipliers) are ``models/blocks.py``'s; this file
holds the config, the Mamba-2 mixer, the attention's scale and the pools; the
family's sparse members (``num_local_experts > 0``) are refused by name.

SERVING. ``for_paged_decode`` gives the module the attention layers' KV
pools, addressed through the block table, and TWO pools of per-slot state:
``ssm_state_pool [mamba layers, 1 + slots, H, P, N]`` and ``ssm_conv_pool
[mamba layers, 1 + slots, (K - 1) (H P + 2 N)]``, row ``1 + s`` decode slot
``s``'s, row 0 what idle rows write, both ``dtype`` (the state's arithmetic
is float32 and the pool rounds once a stored step; a row's state LIES in
its ``H P N`` values as ``[H P / L, N, L]``, lane groups of ``L = 128``
``(head, width)`` columns with the state size down the sublanes, the layout
in which a decode step crosses no lane: ``scan_state_in`` and
``scan_state_out`` hand the scan such rows, and its kernel turns them in
VMEM; the convolution's ``K - 1`` rows lie side by side in ONE pool row: as
``[.., K - 1, channels]`` the chip's compiler tiles the 3 rows to 16 and
copies the whole pool a layer to change its layout, PERF.md PR 49). A
program's row finds
its slot's row as the block table's last entry (the engine's per-slot seam,
``paged_slot_state_for``). ONE mixer, :class:`Mamba2Mixer`, serves a whole
sequence, a prefill chunk and a decode step: a sequence at length 0 starts
from zeros whatever its slot held; the states are taken at each row's
``num_valid``. The recurrence has two forms of one arithmetic: the chunked
scan for ``T > 1`` (``ops/ssd_chunk_scan.py``: a chunk starts from the
slot's stored state and writes it back, so a prompt's state crosses program
calls) and the in-place state update for a decode step
(``ops/ssm_state_update.py``: a Pallas kernel on a TPU, the pool aliased).
"""

import dataclasses
import functools
import math
from typing import Any, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models import blocks
from deepspeed_tpu.models.decode_utils import embed_lookup
from deepspeed_tpu.ops import ssd_chunk_scan, ssm_state_update


# keys a tile of a prefill chunk's attention (``blocks.cached_gqa``): 32 heads
# x 512 queries x 1,024 keys of float32 scores are 67 MB
CHUNK_KEY_TILE = 1024


@dataclasses.dataclass(frozen=True)
class GraniteHybridConfig(blocks.ServedConfig):
    vocab_size: int = 100352
    hidden_size: int = 2048
    num_hidden_layers: int = 40
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    layer_types: Tuple[str, ...] = ()     # "mamba" | "attention"
    # the SwiGLU's width (the source's ``shared_intermediate_size``)
    intermediate_size: int = 8192
    num_local_experts: int = 0
    num_experts_per_tok: int = 0
    mamba_n_heads: int = 64
    mamba_d_head: int = 64
    mamba_d_state: int = 128
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    embedding_multiplier: float = 12.0
    embedding_std: float = 0.02   # the tied embedding is drawn N(0, this)
    residual_multiplier: float = 0.22
    attention_multiplier: float = 0.015625
    logits_scaling: float = 8.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
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
        kinds = set(self.layer_types) - {"mamba", "attention"}
        if len(self.layer_types) != n or kinds:
            raise ValueError(
                f"layer_types needs one of 'mamba' / 'attention' a layer "
                f"({n}), got {self.layer_types}")
        if self.num_local_experts or self.num_experts_per_tok:
            raise ValueError(
                f"num_local_experts {self.num_local_experts}: the "
                "granite_hybrid family serves the dense members only (every "
                "layer's FFN one SwiGLU); its sparse siblings are not "
                "implemented")
        if self.mamba_n_groups != 1:
            raise ValueError(
                f"mamba_n_groups {self.mamba_n_groups}: one group (B and C "
                "shared by every head) is what the mixer implements")
        if self.hidden_size % self.num_attention_heads or (
                self.num_attention_heads % self.num_key_value_heads):
            raise ValueError(
                f"{self.num_attention_heads} heads over hidden "
                f"{self.hidden_size} and {self.num_key_value_heads} KV heads")

    # the contract's (blocks.ServedConfig): the slots' keyword, why
    # kv_dtype is refused, and that no layer is sparse
    slot_knob = "state_slots"
    unquantized = "state-space state has no quantized pool"

    def sparse(self, i: int) -> bool:
        return False

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def mamba_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def conv_width(self) -> int:
        """What the convolution mixes: ``x | B | C``."""
        return self.mamba_inner + 2 * self.mamba_d_state

    def layers_of(self, kind: str):
        """Indices of the layers of one kind, in order: a layer's place in
        its kind's pools is its place here."""
        return [i for i, k in enumerate(self.layer_types) if k == kind]

    def kv_bytes_per_token(self) -> dict:
        """Bytes of keys and values one token keeps (attention layers)."""
        item = jnp.dtype(self.dtype).itemsize
        return {"global": len(self.layers_of("attention"))
                * self.num_key_value_heads * 2 * self.head_dim * item}

    def state_bytes_per_slot(self) -> int:
        """Bytes a decode slot's state takes, all Mamba layers: a matrix a
        head and the convolution's last rows."""
        values = (self.mamba_inner * self.mamba_d_state
                  + (self.mamba_d_conv - 1) * self.conv_width)
        return (len(self.layers_of("mamba")) * values
                * jnp.dtype(self.dtype).itemsize)

    def paged_slot_state_for(self, block_size: int):
        """What a decode slot keeps beside its block table (the engine's
        per-slot seam): one entry of the table, the slot's row of the two
        state pools. None without Mamba layers."""
        if not self.layers_of("mamba"):
            return None
        return {"entries": 1, "knob": self.slot_knob,
                "what": "state-space layers keep a state of fixed size a "
                        "decode slot (a matrix a head and the convolution's "
                        "last rows), written in place every step"}

    def kv_live_bytes(self, live) -> dict:
        """Bytes of per-sequence state a decode step reads, by kind, for
        busy rows of the lengths ``live``: the attention layers' keys and
        values of every token, the Mamba layers' state a busy slot."""
        return {"global": int(live.sum())
                * self.kv_bytes_per_token()["global"],
                "state": len(live) * self.state_bytes_per_slot()}

    @staticmethod
    def tiny(**kw):
        """The CPU tests' size: every mechanism, no published width."""
        base = dict(vocab_size=128, hidden_size=64, num_hidden_layers=5,
                    num_attention_heads=8, num_key_value_heads=2,
                    layer_types=("mamba", "mamba", "attention", "mamba",
                                 "mamba"),
                    intermediate_size=128, mamba_n_heads=8, mamba_d_head=16,
                    mamba_d_state=32, mamba_chunk_size=4,
                    max_position_embeddings=256)
        base.update(kw)
        return GraniteHybridConfig(**base)


# ---------------------------------------------------------------------------
# Mamba-2's own initialisers: at N(0, 0.02) every head would forget within
# two positions (``a`` = 0.5) and nothing would tell a state carried from a
# state lost

def _a_log_init(key, shape, dtype):
    return jnp.log(jax.random.uniform(key, shape, jnp.float32, 1.0,
                                      16.0)).astype(dtype)


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


def step_terms(cfg, xbc, dt, dt_bias, a_log):
    """What the recurrence reads of the convolved ``xBC`` and of ``dt``,
    float32: ``(x [B, T, H, P], b [B, T, N], c [B, T, N], delta [B, T, H],
    A [H])``."""
    inner, n = cfg.mamba_inner, cfg.mamba_d_state
    x = xbc[..., :inner].reshape(*xbc.shape[:2], cfg.mamba_n_heads,
                                 cfg.mamba_d_head)
    delta = jax.nn.softplus(dt + dt_bias.astype(jnp.float32))
    return (x, xbc[..., inner:inner + n], xbc[..., inner + n:], delta,
            -jnp.exp(a_log.astype(jnp.float32)))


def state_in(pool, index, rows, fresh):
    """The state a paged call's rows start from, of layer ``index`` of one
    of the state pools: what their slots hold (``rows [B]`` into the pool),
    and zeros for a sequence at length 0 (``fresh [B]``), whatever its
    slot's last tenant left."""
    held = pool[index, rows]
    return jnp.where(fresh.reshape(-1, *(1,) * (held.ndim - 1)),
                     jnp.zeros_like(held), held)


def scan_state_in(pool, index, rows, fresh):
    """:func:`state_in` of ``ssm_state_pool`` as its values LIE: the pool is
    ALLOCATED ``[layers, rows, H, P, N]`` and a row's values lie as ``[H P /
    L, N, L]``, the state size down the sublanes (``ops/ssm_state_update``:
    the layout in which a decode step crosses no lane). -> ``[B, H P / L,
    N, L]``, what ``ssd_chunk_scan`` takes and returns."""
    return state_in(ssm_state_update.lane_view(pool), index, rows, fresh)


def scan_state_out(pool, index, rows, state):
    """The pool with ``state [B, H P / L, N, L]`` in ``rows`` of layer
    ``index`` (:func:`scan_state_in`'s layout)."""
    view = ssm_state_update.lane_view(pool)
    return view.at[index, rows].set(state.astype(pool.dtype)).reshape(
        pool.shape)


class Mamba2Mixer(nn.Module):
    """``u [B, T, d] -> (its term [B, T, d], pools)``: the Mamba-2 mixer of
    a whole sequence from zeros (plain call), of a whole prompt or a
    prefill chunk from the slot's stored state, and of a decode step
    (``T = 1``) on the pool in place. ``index``: the layer's place among
    the Mamba layers (its row of the state pools); ``work``:
    ``ssm_state_update.busy_rows`` of this step, or None."""

    config: GraniteHybridConfig

    @nn.compact
    def __call__(self, u, paging=None, pools=None, index=0, work=None):
        from deepspeed_tpu.ops.attention import (record_dispatch,
                                                 use_decode_kernel)

        cfg = self.config
        b, t, d = u.shape
        f32 = jnp.float32
        inner, heads = cfg.mamba_inner, cfg.mamba_n_heads
        w_in = self.param("in_proj", blocks.init(),
                          (d, 2 * inner + 2 * cfg.mamba_d_state + heads),
                          cfg.param_dtype)
        taps = self.param("conv", _taps_init,
                          (cfg.conv_width, cfg.mamba_d_conv), cfg.param_dtype)
        conv_bias = self.param("conv_bias", _taps_init, (cfg.conv_width,),
                               cfg.param_dtype)
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,),
                             cfg.param_dtype)
        a_log = self.param("A_log", _a_log_init, (heads,), cfg.param_dtype)
        skip = self.param("D", nn.initializers.ones, (heads,),
                          cfg.param_dtype)
        # [z | xBC | dt], kept float32: dt sets a decay that hundreds of
        # positions compound
        zxd = jnp.dot(u, w_in.astype(cfg.dtype), preferred_element_type=f32)
        z, xbc, dt = (zxd[..., :inner], zxd[..., inner:-heads],
                      zxd[..., -heads:])
        serving = cfg.serving
        if serving:
            rows = paging["block_tables"][:, -1]
            fresh = paging["lengths"] == 0
            num_valid = paging["num_valid"]
            conv_state = state_in(pools["ssm_conv_pool"], index, rows,
                                  fresh).reshape(b, -1, cfg.conv_width)
        else:
            num_valid = jnp.full((b,), t, jnp.int32)
            conv_state = jnp.zeros((b, cfg.mamba_d_conv - 1, cfg.conv_width),
                                   f32)
        with jax.named_scope("ssm._conv"):
            conv, conv_state = blocks.causal_conv(xbc, taps, conv_state,
                                                  num_valid)
            xbc = nn.silu(conv + conv_bias.astype(f32))
        x, bm, cm, delta, rate = step_terms(cfg, xbc, dt, dt_bias, a_log)
        # a position past the row's last real one leaves the state as it is
        delta = jnp.where(jnp.arange(t)[None, :, None]
                          < num_valid[:, None, None], delta, 0.0)
        if serving and t == 1 and not paging.get("prefill"):
            # a decode step: the pool in place
            kernel = use_decode_kernel() and ssm_state_update.kernel_serves(
                heads, cfg.mamba_d_head, cfg.mamba_d_state)
            record_dispatch("granite_ssm_decode_"
                            + ("kernel" if kernel else "xla"))
            decay = jnp.where(fresh[:, None], 0.0, jnp.exp(delta[:, 0] * rate))
            update = (functools.partial(ssm_state_update.state_update_kernel,
                                        work=work) if kernel
                      else ssm_state_update.state_update_xla)
            with jax.named_scope("ssm._state_update"):
                y, state_pool = update(
                    pools["ssm_state_pool"], index, rows, decay,
                    delta[:, 0, :, None] * x[:, 0], bm[:, 0], cm[:, 0])
            y = y[:, None]
        else:
            if serving:
                record_dispatch("granite_ssm_prefill_chunk")
                state = scan_state_in(pools["ssm_state_pool"], index, rows,
                                      fresh)
            else:
                state = ssm_state_update.to_lanes(jnp.zeros(
                    (b, heads, cfg.mamba_d_head, cfg.mamba_d_state), f32))
            y, state = ssd_chunk_scan.ssd_chunk_scan(
                x, delta, rate, bm, cm, state, cfg.mamba_chunk_size,
                cfg.dtype)
            if serving:
                state_pool = scan_state_out(pools["ssm_state_pool"], index,
                                            rows, state)
        if serving:
            pool = pools["ssm_conv_pool"]
            pools = {**pools, "ssm_state_pool": state_pool,
                     "ssm_conv_pool": pool.at[index, rows].set(
                         conv_state.reshape(b, -1).astype(pool.dtype))}
        y = y + skip.astype(f32)[:, None] * x
        # the gate BEFORE the norm, the norm over the whole inner width
        gated = y.reshape(b, t, inner) * nn.silu(z)
        normed = blocks.RMSNorm(cfg.rms_norm_eps, cfg.dtype,
                                name="norm")(gated)
        return blocks.dense(cfg, "out_proj", d)(normed), pools


class GraniteAttention(nn.Module):
    config: GraniteHybridConfig

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
        # scores are q k^T * attention_multiplier, and every attention path
        # of blocks.py scales by dk ** -0.5: the queries take the ratio. At
        # the published sizes it is 1/8, a power of two, so the product is
        # exact in any float type; a ``scale`` argument through paged_gqa,
        # masked_gqa and the kernel would have changed three served
        # families' programs for a number one family has
        q = q * jnp.asarray(cfg.attention_multiplier * dh ** 0.5, q.dtype)
        if not cfg.serving:
            y = blocks.causal_gqa(q, k, v)
        else:
            # no rotation: positions only address the cache (the table's
            # last entry is the state row)
            pos = blocks.call_positions(cfg, paging, t)
            table = paging["block_tables"][:, :-1]
            # a chunk's keys a tile at a time, at most half the table's
            table_keys = table.shape[-1] * cfg.paged_block_size
            y, k_pool, v_pool = blocks.paged_gqa(
                q, k, v, pos, paging, table, pools["global_key_pool"],
                pools["global_value_pool"], index, "granite_attn", work=work,
                key_tile=min(CHUNK_KEY_TILE, table_keys // 2))
            pools = {**pools, "global_key_pool": k_pool,
                     "global_value_pool": v_pool}
        out = proj("o_proj", cfg.hidden_size)(y.reshape(b, t, heads * dh))
        return out, pools


class GraniteHybridForCausalLM(blocks.PagedDecoder):
    """``blocks.PagedDecoder`` over the layer types; the head is the
    embedding. No layer is sparse: the counters it hands back are zeros,
    and it returns no routed sets."""

    config: GraniteHybridConfig
    tied = True
    serve_routed = False
    # the engine reads this leaf's layout to choose ``paging["lookup"]``
    lookup_table = "embed_tokens"

    def lookup(self, table, ids, paging):
        return embed_lookup(table, ids, (paging or {}).get("lookup", "rows"))

    def pool_shapes(self, num_blocks, block_size):
        """A key and a value pool of the attention layers (``[layers,
        blocks, block_size, kv_heads * head_dim]``, the engine's
        ``num_blocks``), and the Mamba layers' two state pools (row 0 for
        idle rows, then a row a slot)."""
        cfg = self.config
        shapes = {}
        attn, mamba = (len(cfg.layers_of(k)) for k in ("attention", "mamba"))
        if attn:
            row = (attn, num_blocks, block_size,
                   cfg.num_key_value_heads * cfg.head_dim)
            shapes["global_key_pool"] = shapes["global_value_pool"] = row
        if mamba:
            slots = 1 + cfg.paged_state_slots
            shapes["ssm_state_pool"] = (mamba, slots, cfg.mamba_n_heads,
                                        cfg.mamba_d_head, cfg.mamba_d_state)
            shapes["ssm_conv_pool"] = (
                mamba, slots, (cfg.mamba_d_conv - 1) * cfg.conv_width)
        return shapes

    def step_work(self, paging):
        """The grids of this step's kernels, each the same for every layer
        of its kind: the attention's follows the lengths, the state
        update's the busy rows."""
        from deepspeed_tpu.ops.hybrid_decode_attention import (
            hybrid_plan, hybrid_work_list)

        cfg = self.config
        tables = paging["block_tables"]
        lanes = cfg.num_key_value_heads * cfg.head_dim
        plan = hybrid_plan(cfg.paged_block_size, lanes, lanes,
                           tables.shape[-1] - 1)
        return (hybrid_work_list(paging["lengths"], tables, plan),
                ssm_state_update.busy_rows(tables[:, -1]))

    def mixer(self, i, u, paging, pools, work):
        cfg = self.config
        kind = cfg.layer_types[i]
        place = cfg.layers_of(kind).index(i)
        attn_work, ssm_work = work or (None, None)
        if kind == "attention":
            return GraniteAttention(cfg, name=f"layers_{i}_attn")(
                u, paging, pools, place, attn_work)
        return Mamba2Mixer(cfg, name=f"layers_{i}_mamba")(
            u, paging, pools, place, ssm_work)
