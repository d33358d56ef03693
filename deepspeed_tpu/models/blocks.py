"""The decoder blocks the served families are composed of, each written
once: the leaf blocks, the attention arithmetic, the sparse FFN, and the
paged contract, what a served family owes ``serving/engine.py``
(:class:`ServedConfig`, :class:`PagedDecoder`). A family file holds its
config, its token mixer, the rows it keeps in the pool and its per-layer
pattern. Arrows point one way: this file imports no family and names none,
and no family imports another.
"""

import dataclasses
from typing import Any, Optional

import flax.linen as nn
import jax
import jax.numpy as jnp

from deepspeed_tpu.models.decode_utils import (paged_positions,
                                               paged_write_slots)
from deepspeed_tpu.moe import dropless

_NEG = -1e30
# queries a chunk of the masked XLA attention of a whole prompt: 64 heads x
# 512 queries x 4096 keys of float32 scores are 0.5 GB
_QUERY_CHUNK = 512


# ---------------------------------------------------------------------------
# leaf blocks

def init(scale=0.02):
    return nn.initializers.normal(stddev=scale)


def dense(of, name, width, std=0.02):
    """The bias-free projection every block is made of, in the ``dtype`` and
    ``param_dtype`` that ``of`` (a config, a block) says."""
    return nn.Dense(width, use_bias=False, dtype=of.dtype,
                    param_dtype=of.param_dtype, kernel_init=init(std),
                    name=name)


class RMSNorm(nn.Module):
    """Root-mean-square layernorm (no mean subtraction, no bias)."""

    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],),
                           jnp.float32)
        x32 = x.astype(jnp.float32)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1,
                                           keepdims=True) + self.eps)
        return (x32 * scale).astype(self.dtype)


def rope_frequencies(head_dim: int, positions, theta: float):
    """cos/sin tables for the given absolute positions: [..., head_dim//2]."""
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, jnp.float32)
                           / head_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv  # [..., hd/2]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x, cos, sin):
    """x: [B, T, H, D]; cos/sin: [T, D/2] shared or [B, T, D/2] per-row
    (left-padded batches). Rotates pairs (x_even, x_odd) — the interleaved
    convention HF Llama uses after its half-split equivalence."""
    x1, x2 = jnp.split(x, 2, axis=-1)  # HF half-split convention
    if cos.ndim == 2:
        cos, sin = cos[None], sin[None]
    c = cos[:, :, None, :]
    s = sin[:, :, None, :]
    return jnp.concatenate(
        [x1 * c - x2 * s, x2 * c + x1 * s], axis=-1).astype(x.dtype)


class SwiGLU(nn.Module):
    """``down(silu(gate x) * up x)`` of one width, back to ``hidden``."""

    width: int
    hidden: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    limit: float = 0.0  # > 0: the clamped form (``dropless.glu``)

    @nn.compact
    def __call__(self, x):
        g = dense(self, "gate_proj", self.width)(x)
        u = dense(self, "up_proj", self.width)(x)
        return dense(self, "down_proj", self.hidden)(
            dropless.glu(g, u, self.limit))


# ---------------------------------------------------------------------------
# attention arithmetic

def call_positions(cfg, paging, t: int):
    """Absolute positions of a call's ``t`` tokens: ``[B, T]`` from each
    row's length under a serving config, ``[1, T]`` from 0 otherwise."""
    if not cfg.serving:
        return jnp.arange(t, dtype=jnp.int32)[None]
    if paging is None:
        raise ValueError(
            "paged decode needs the `paging` call argument: "
            '{"block_tables", "lengths", "num_valid", "prefill"}')
    return paged_positions(paging["lengths"], t)


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


def paged_gqa(q, k, v, pos, paging, table, k_pool, v_pool, index, label,
              sink=None, work=None, key_tile: int = 0, value_group: int = 1):
    """One layer's grouped-query step of a serving program over rows a
    block table addresses: write this call's keys and values into layer
    ``index`` of the pools (``[layers, blocks, block_size, kv_heads *
    width]``) through ``table [B, blocks a sequence]``, and attend. A whole
    prompt (``paging["prefill"]``) attends over its own keys; a decode step
    on a TPU runs the paged kernel over the work list ``work``; a prompt's
    later chunk, and every step where no TPU is, takes :func:`cached_gqa`
    (``key_tile`` is its). ``label`` prefixes what ``record_dispatch``
    counts (the caller's: ``stats()`` reads it). ``value_group``: a head
    keeps the values of that many adjacent KV heads side by side
    (:func:`value_groups`).
    -> ``(y [B, T, H, value_group * dv], k_pool, v_pool)``."""
    from deepspeed_tpu.ops.attention import record_dispatch, use_decode_kernel
    from deepspeed_tpu.ops.hybrid_decode_attention import (
        decode_attention_hybrid)

    b, t = q.shape[:2]
    kv, bs = k.shape[2], k_pool.shape[2]
    blk, off = paged_write_slots(table, pos, paging["num_valid"], bs)
    k_pool = k_pool.at[index, blk, off].set(k.reshape(b, t, -1))
    v_pool = v_pool.at[index, blk, off].set(v.reshape(b, t, -1))
    if paging.get("prefill"):
        record_dispatch(f"{label}_prefill_xla")
        y = causal_gqa(q, k, value_groups(v, value_group), 0, sink)
    elif t == 1 and use_decode_kernel():
        record_dispatch(f"{label}_decode_kernel")
        with jax.named_scope("attn._hybrid_kv_attend"):
            y = decode_attention_hybrid(
                q, k_pool, v_pool, table, paging["lengths"], index,
                kv_heads=kv, sink=sink, work=work, value_group=value_group)
    else:
        # (the masked XLA path over the sequence's blocks, and its form
        # in tiles of keys, are at the file's end, below the call sites
        # whose line numbers a kernel's lowered text carries)
        y = cached_gqa(
            q, pos, paging, table,
            (k_pool, v_pool, index),
            (kv, k.shape[-1], v.shape[-1]),
            label, sink, key_tile, value_group)
    return y, k_pool, v_pool


# ---------------------------------------------------------------------------
# the sparse FFN

class SparseFFN(nn.Module):
    """The sparse FFN: the router over ALL ``experts`` published, the expert
    weights of the share held here (rank ``ep_rank`` of ``ep_size``:
    ``moe/dropless.py``), and the shared experts where ``shared_width``
    says there are any, one SwiGLU of their summed width that every share
    computes alike. ``scoring``, ``renormalize``, ``norm_eps``, ``scale``,
    ``n_group`` and ``topk_group`` are ``dropless.route``'s; ``limit`` and
    ``shared_limit`` clamp the experts' and the shared SwiGLU
    (``dropless.glu``); ``bias_std`` None is no selection bias (else the
    std it is drawn with: a balancing term that training moves from zero).

    Takes the float32 norm and returns the float32 sum of the held experts'
    terms, the layer's counters and the experts each token chose ``[B, T,
    k]``: ``(y, counters, chosen)``, or ``(y, shared, counters, chosen)``
    with shared experts, the two terms apart so that shares can be summed
    with the shared term counted once."""

    experts: int
    top_k: int
    width: int
    dtype: Any = jnp.bfloat16
    param_dtype: Any = jnp.float32
    scoring: str = "sigmoid"
    renormalize: bool = True
    norm_eps: float = 0.0
    scale: float = 1.0
    bias_std: Optional[float] = None
    shared_width: int = 0
    ep_rank: int = 0
    ep_size: int = 1
    n_group: int = 1
    topk_group: int = 1
    limit: float = 0.0
    shared_limit: float = 0.0

    @nn.compact
    def __call__(self, x, valid=None):
        b, t, d = x.shape
        first, count = dropless.held_range(self.experts, self.ep_rank,
                                           self.ep_size)
        f, dtype = self.width, self.dtype
        # (created in this order: a scope draws its parameters from a
        # counter, so the order decides the values a seed gives)
        router = self.param("router", init(), (d, self.experts),
                            self.param_dtype)
        bias = None if self.bias_std is None else self.param(
            "router_bias", init(self.bias_std), (self.experts,),
            self.param_dtype)
        gate = self.param("gate", init(), (count, d, f), self.param_dtype)
        up = self.param("up", init(), (count, d, f), self.param_dtype)
        down = self.param("down", init(), (count, f, d), self.param_dtype)
        rows = x.reshape(b * t, d)
        # the gate reads the float32 norm itself, the experts ``dtype``
        # (both calls go through the module: controls replace them there)
        experts, weights = dropless.route(
            rows, router, bias, self.top_k, norm_eps=self.norm_eps,
            scale=float(self.scale), scoring=self.scoring,
            renormalize=self.renormalize, n_group=self.n_group,
            topk_group=self.topk_group)
        rows = rows.astype(dtype)
        y, counters = dropless.expert_ffn(
            rows, experts, weights, gate.astype(dtype), up.astype(dtype),
            down.astype(dtype), first_expert=first, n_routed=self.experts,
            valid=None if valid is None else valid.reshape(b * t),
            limit=self.limit)
        shared = ()
        if self.shared_width:
            shared = (SwiGLU(self.shared_width, d, dtype, self.param_dtype,
                             self.shared_limit,
                             name="shared_experts")(x.astype(dtype)),)
        return (y.reshape(b, t, d), *(s.astype(jnp.float32) for s in shared),
                counters, experts.reshape(b, t, -1))


# ---------------------------------------------------------------------------
# the paged contract: what ``ServingEngine`` asks of a served model

class ServedConfig:
    """The config's half of the contract, mixed into a family's frozen
    dataclass. ``ServingEngine`` (``serving/engine.py``) reads of a config:

    - ``for_paged_decode(num_blocks, block_size, **knobs)`` -> the serving
      variant (here). ``knobs``: ``kv_dtype`` where the engine's config
      names one, ``return_routed`` where it keeps routed sets, and
      ``<slot_knob>=decode slots`` where a slot keeps state;
    - ``routed_width`` (here), ``n_head`` (here), ``max_position_embeddings``;
    - the family's own, each optional: ``paged_slot_state_for(block_size)``
      -> None or ``{"entries", "knob", "what"}``, state of fixed size a
      decode slot keeps beside its block table (``entries`` of the table a
      slot hands its programs address it, after the sequence's blocks). A
      slot that keeps TWO kinds of state (a ring of blocks of one pool and
      a row of another) also says ``"parts"``: how many of the ``entries``
      each kind takes, in the table's order; each part counts its own pool
      from 1 (slot ``s``: ``1 + s * part ..``), under the ONE knob;
      ``paged_row_kind()`` -> ``{"kind", "what"}`` where a pool row is no
      keys and values by heads; ``kv_bytes_per_token()``, bytes a token
      KEEPS by kind of row, and ``kv_live_bytes(live)``, bytes a decode
      step READS by kind: a pool counts once for EACH LAYER THAT READS IT
      (a cache several layers share is kept once and read by each).

    The family's dataclass declares ``vocab_size``, ``hidden_size``,
    ``num_hidden_layers``, ``num_attention_heads``, ``intermediate_size``,
    ``num_experts_per_tok``, ``dtype``, ``param_dtype`` and the serving
    fields ``decode``, ``paged``, ``paged_num_blocks``, ``paged_block_size``,
    ``paged_return_routed``, ``paged_<slot_knob>``; it says ``sparse(i)``
    (is layer ``i``'s FFN sparse) and ``sparse_ffn()`` (:class:`SparseFFN`'s
    arguments; where they are a layer's own, ``sparse_ffn_at(i)``), and may
    set the shell's scalars and ``embedding_std``."""

    # for_paged_decode's keyword for the decode slots (the seam's ``knob``)
    slot_knob = None
    unquantized = "rows have no quantized pool"  # kv_dtype: "this model's"
    # the shell's (:class:`PagedDecoder`): scalars (at 1 nothing is traced)
    embedding_multiplier = residual_multiplier = logits_scaling = 1.0
    embedding_std = 0.02  # and what its embedding is drawn with, N(0, std)

    @property
    def n_head(self) -> int:
        return self.num_attention_heads

    @property
    def serving(self) -> bool:
        return self.decode and self.paged

    @property
    def sparse_layers(self) -> int:
        return sum(bool(self.sparse(i))
                   for i in range(self.num_hidden_layers))

    @property
    def routed_width(self) -> int:
        """Experts a token chooses over all its sparse layers: the width
        of a row of what ``paged_return_routed`` returns."""
        return self.sparse_layers * self.num_experts_per_tok

    def sparse_ffn_at(self, i: int) -> dict:
        """:class:`SparseFFN`'s arguments for layer ``i``: every sparse
        layer's alike, unless the family says a layer's own (a clamp that
        only some layers have)."""
        return self.sparse_ffn()

    def pool_dims(self):
        """``(paged_num_blocks, paged_block_size)``, checked (block 0 is
        the garbage block)."""
        nb, bs = self.paged_num_blocks, self.paged_block_size
        if nb <= 1 or bs <= 0:
            raise ValueError(f"paged decode needs paged_num_blocks > 1 (got "
                             f"{nb}) and paged_block_size > 0 (got {bs})")
        return nb, bs

    def for_paged_decode(self, num_blocks: int, block_size: int,
                         kv_dtype: str = "", return_routed: bool = False,
                         **slots):
        """Serving variant. ``num_blocks`` sizes the pool the block tables
        address (block 0 the garbage block); ``<slot_knob>=n`` gives ``n``
        decode slots their state; with ``return_routed`` a call also
        returns every token's chosen experts (:class:`PagedDecoder`)."""
        if set(slots) - {self.slot_knob}:
            raise TypeError(f"for_paged_decode got {sorted(slots)}")
        if kv_dtype:
            raise ValueError(f"kv_cache_dtype {kv_dtype!r}: this model's "
                             f"{self.unquantized}")
        n = int(slots.get(self.slot_knob, 0))
        state = self.slot_knob and self.paged_slot_state_for(block_size)
        if state and n < 1:
            raise ValueError(f"{state['what']}: for_paged_decode needs "
                             f"{self.slot_knob}")
        kept = {f"paged_{self.slot_knob}": n} if self.slot_knob else {}
        return dataclasses.replace(
            self, decode=True, paged=True, paged_num_blocks=int(num_blocks),
            paged_block_size=int(block_size),
            paged_return_routed=bool(return_routed), **kept)


def paged_valid(paging, t: int):
    """``[B, T]``: the tokens of a paged call that are tokens. A bucket's
    padding and an idle slot's row are none: they route nowhere."""
    return ((jnp.arange(t)[None] < paging["num_valid"][:, None])
            & (paging["block_tables"][:, :1] != 0))


class PagedDecoder(nn.Module):
    """The module's half of the contract, the decoder shell: embedding (x
    ``embedding_multiplier``) -> per layer the family's mixer and a dense or
    sparse FFN, pre-norm, each term x ``residual_multiplier`` onto a float32
    stream -> final norm (``norm_class``: RMSNorm) -> tied or untied head (/
    ``logits_scaling``).

    A mixer hands a LATER layer something of this call through ``pools``
    (a key that is no pool: the shell writes back only the pools it
    declared): the keys a layer's queries chose (``selected``), a layer's
    scan output (``memory``). Where :meth:`rows_from` says so, a paged call
    of ``T > 1`` carries ONE row a sequence from that layer on (the row at
    ``num_valid - 1``, ``pools["row_at"]``): the later layers and the head
    see ``[B, 1, d]``, and the logits are ``[B, 1, vocab]``.

    Plain call: ``[B, T, vocab]`` float32 logits. Paged (serving) call,
    ``paging = {"block_tables", "lengths", "num_valid", "prefill"}`` with
    the ``cache`` collection mutable: ``(logits, {"counters": int32[4]})``,
    the sparse layers' counters of this call summed (``dropless.COUNTERS``;
    the serving programs hand them back with the tokens); under
    ``paged_return_routed`` also ``"routed": int32[B, T, sparse layers x
    k]``, each token's chosen experts layer by layer (a padded row's are
    meaningless). ``ServingEngine`` reads of the class ``serve_counters``,
    ``serve_routed``, and ``lookup_table`` where :meth:`lookup` takes
    ``paging["lookup"]``. A family says the three methods and the rest."""

    config: Any
    # what the serving engine's ledger names the counters by
    serve_counters = dropless.COUNTERS
    # for_paged_decode takes ``return_routed``
    serve_routed = True
    # the two norms of a layer (``layers_<i>_<name>``), their epsilon's
    # field in the config and their class (``(eps, dtype, name=)``)
    norms = ("input_layernorm", "post_attention_layernorm")
    eps_field = "rms_norm_eps"
    norm_class = RMSNorm
    # a plain call's ``pools`` is an empty dict, not None: its mixers hand
    # a later layer something of the call there too
    carries = False
    # the head is the embedding
    tied = False
    # the dense FFN's parameter type (None: the config's ``param_dtype``)
    dense_param_dtype = None
    # a serving pool whose type is not the config's ``dtype`` (a float32
    # state beside bfloat16 rows), by name
    pool_dtypes = {}

    def pool_shapes(self, num_blocks: int, block_size: int) -> dict:
        """``{name: shape}`` of the serving pools (``cache`` variables of
        ``config.dtype``, or of ``pool_dtypes[name]``), declared once by
        the model."""
        raise NotImplementedError

    def more_counters(self, routed, valid, pools):
        """What the family counts itself of a paged call: of its routed
        sets (``routed``: each sparse layer's ``[B, T, k]``; ``valid [B,
        T]``) or of what its mixers left in ``pools`` beside the pools;
        ``int32[n]`` handed back behind the sparse layers' four and named
        by the tail of its ``serve_counters``; None: the four alone."""
        return None

    def step_work(self, paging):
        """What every layer's decode kernel shares of one step (the work
        lists: the grids follow this step's lengths), made once."""
        raise NotImplementedError

    def mixer(self, i: int, u, paging, pools, work):
        """Layer ``i``'s token mixer on the normed stream ``u`` ->
        ``(its term, pools)``; ``pools`` the pools' values by name (None in
        a plain call), ``work`` :meth:`step_work`'s or None."""
        raise NotImplementedError

    def lookup(self, table, ids, paging):
        return table[ids]

    def rows_from(self, i: int) -> bool:
        """Whether layer ``i`` is the first that a paged call of ``T > 1``
        runs for ONE row a sequence (the row at ``num_valid - 1``): a model
        whose later layers only read what the earlier ones cached needs
        them, and the head, for a prompt's last position alone. No layer:
        every position runs through every layer."""
        return False

    @nn.compact
    def __call__(self, input_ids, deterministic=True, paging=None):
        cfg = self.config
        paged = cfg.serving
        # (``embed_tokens`` first and ``lm_head`` last: the order decides
        # the values a seed gives)
        embed = self.param("embed_tokens", init(cfg.embedding_std),
                           (cfg.vocab_size, cfg.hidden_size), cfg.param_dtype)
        x = self.lookup(embed, input_ids, paging).astype(cfg.dtype)
        t = input_ids.shape[1]
        pools = valid = work = None
        if self.carries:
            pools = {}
        if paged:
            variables = {
                name: self.variable("cache", name, jnp.zeros, shape,
                                    self.pool_dtypes.get(name, cfg.dtype))
                for name, shape in self.pool_shapes(*cfg.pool_dims()).items()}
            pools = {name: var.value for name, var in variables.items()}
            valid = paged_valid(paging, t)
            if t == 1 and not paging.get("prefill"):
                from deepspeed_tpu.ops.attention import use_decode_kernel

                if use_decode_kernel():
                    work = self.step_work(paging)
        counters = jnp.zeros((len(dropless.COUNTERS),), jnp.int32)
        routed = []
        # the residual stream and every norm are float32 (bfloat16 would
        # round the stream once a layer, and a norm's rounding moves the
        # router's near ties); what a matmul reads is cfg.dtype
        x = _scaled(x.astype(jnp.float32), cfg.embedding_multiplier)
        norm = lambda name: self.norm_class(getattr(cfg, self.eps_field),
                                            jnp.float32, name=name)
        for i in range(cfg.num_hidden_layers):
            scope = f"layers_{i}"
            if paged and t > 1 and self.rows_from(i):
                x, valid, pools = _last_rows(x, valid, pools, paging)
            a, pools = self.mixer(
                i, norm(f"{scope}_{self.norms[0]}")(x).astype(cfg.dtype),
                paging, pools, work)
            x = x + _scaled(a.astype(jnp.float32), cfg.residual_multiplier)
            h = norm(f"{scope}_{self.norms[1]}")(x)
            if cfg.sparse(i):
                y, *shared, c, chosen = SparseFFN(
                    **cfg.sparse_ffn_at(i), name=f"{scope}_mlp")(h, valid)
                for term in shared:
                    y = y + term
                counters = counters + c
                routed.append(chosen)
            else:
                y = SwiGLU(cfg.intermediate_size, cfg.hidden_size, cfg.dtype,
                           self.dense_param_dtype or cfg.param_dtype,
                           name=f"{scope}_mlp")(h.astype(cfg.dtype))
            x = x + _scaled(y.astype(jnp.float32), cfg.residual_multiplier)
        if paged:
            for name, var in variables.items():
                var.value = pools[name]
        x = norm("norm")(x).astype(cfg.dtype)
        head = embed if self.tied else self.param(
            "lm_head", init(), (cfg.vocab_size, cfg.hidden_size),
            cfg.param_dtype)
        logits = jnp.einsum("btc,vc->btv", x, head.astype(cfg.dtype),
                            preferred_element_type=jnp.float32)
        logits = _scaled(logits, 1.0 / cfg.logits_scaling)
        if not paged:
            return logits
        more = self.more_counters(routed, valid, pools)
        if more is not None:
            counters = jnp.concatenate([counters, more])
        aux = {"counters": counters}
        if "selected" in pools:     # the keys each layer's queries chose
            aux["selected"] = jnp.stack(pools["selected"], axis=2)
        if cfg.paged_return_routed and routed:
            aux["routed"] = jnp.concatenate(routed, axis=-1)
        return logits, aux


# ---------------------------------------------------------------------------
# (below the call sites above, whose line numbers a kernel's lowered text
# carries: new code goes here)

def _scaled(x, scale):
    """``x * scale``; at 1 ``x`` itself, so a config without the multiplier
    traces the operations it always did."""
    return x if scale == 1 else x * scale


def causal_conv(z, taps, state, num_valid):
    """The depthwise causal convolution and the state it leaves.

    ``z [B, T, C]``: this call's positions; ``taps [C, L]``, the last of
    which meets the current position; ``state [B, L - 1, C]``: ``z`` at the
    ``L - 1`` positions before this call's first; ``num_valid [B]``: the
    real positions of each row (a bucket's padding lies behind them).
    -> ``(c [B, T, C] float32, new state [B, L - 1, C])``: the state after
    the row's LAST REAL position (the old one where it has none)."""
    t, keep = z.shape[1], taps.shape[1] - 1
    line = jnp.concatenate([state.astype(z.dtype), z], axis=1)
    w = taps.astype(jnp.float32)
    c = sum(w[None, None, :, j] * line[:, j:j + t].astype(jnp.float32)
            for j in range(keep + 1))
    at = num_valid[:, None] + jnp.arange(keep, dtype=jnp.int32)[None]
    return c, jnp.take_along_axis(line, at[..., None], axis=1)


def value_groups(v, value_group: int):
    """``v [B, S, KV, dv]`` as each KV head's queries keep it: its own
    values (``value_group`` 1: ``v`` itself), or those of the
    ``value_group`` ADJACENT KV heads its head belongs to, side by side:
    ``[B, S, KV, value_group * dv]``, KV head ``h`` holding heads
    ``value_group * (h // value_group) ..`` (differential attention: either
    key of a pair weighs ``[v1 | v2]``)."""
    if value_group == 1:
        return v
    b, s, kv, dv = v.shape
    wide = v.reshape(b, s, kv // value_group, value_group * dv)
    return jnp.repeat(wide, value_group, axis=2)


def cached_gqa(q, pos, paging, table, pools, widths, label, sink=None,
               key_tile: int = 0, value_group: int = 1):
    """Queries ``q [B, T, H, dk]`` at positions ``pos [B, T]`` over the
    keys and values their sequences keep in layer ``index`` of ``pools =
    (k_pool, v_pool, index)`` through ``table``; ``widths = (kv_heads, dk,
    dv)``; ``value_group``: :func:`value_groups`. -> ``[B, T, H,
    value_group * dv]``.

    The plain form gathers EVERY block the table has room for and masks
    (``<label>_cached_xla``): a 512-token chunk of an 8,192-token table
    scores 8,192 keys whatever the sequence's length. With ``key_tile``
    (keys, whole blocks, dividing the table; no sink) the keys are taken a
    tile at a time under an online softmax, as many tiles as the call's
    longest row has keys (``lengths + num_valid``: a traced count), so a
    chunk at position 1,024 does not pay for the table
    (``<label>_cached_tiled_xla``)."""
    from deepspeed_tpu.ops.attention import record_dispatch

    k_pool, v_pool, index = pools
    kv, dk, dv = widths
    b, t, heads, _ = q.shape
    bs = k_pool.shape[2]
    per = key_tile // bs
    if not (key_tile and sink is None and t > 1 and per
            and key_tile % bs == 0 and table.shape[-1] % per == 0):
        record_dispatch(f"{label}_cached_xla")
        rows = table.shape[-1] * bs
        key_pos = jnp.broadcast_to(
            jnp.arange(rows, dtype=jnp.int32)[None], (b, rows))
        return masked_gqa(
            q, k_pool[index, table].reshape(b, rows, kv, dk),
            value_groups(v_pool[index, table].reshape(b, rows, kv, dv),
                         value_group),
            pos, key_pos, None, 0, sink)
    record_dispatch(f"{label}_cached_tiled_xla")
    group = heads // kv
    qg = q.reshape(b, t, kv, group, dk)
    ends = paging["lengths"] + paging["num_valid"]
    tiles = (jnp.max(ends) + key_tile - 1) // key_tile
    offsets = jnp.arange(key_tile, dtype=jnp.int32)

    def one_tile(j, carry):
        m, l, acc = carry
        blocks = jax.lax.dynamic_slice_in_dim(table, j * per, per, axis=1)
        keys = k_pool[index, blocks].reshape(b, key_tile, kv, dk)
        values = value_groups(
            v_pool[index, blocks].reshape(b, key_tile, kv, dv), value_group)
        s = jnp.einsum("btkgd,bskd->bkgts", qg, keys,
                       preferred_element_type=jnp.float32) * dk ** -0.5
        seen = ((j * key_tile + offsets)[None, None, :]
                <= pos[:, :, None])[:, None, None]           # [B,1,1,T,S]
        s = jnp.where(seen, s, _NEG)
        m_new = jnp.maximum(m, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.where(seen, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        acc = acc * alpha + jnp.einsum(
            "bkgts,bskd->bkgtd", p.astype(values.dtype), values,
            preferred_element_type=jnp.float32)
        return m_new, alpha * l + jnp.sum(p, axis=-1, keepdims=True), acc

    lead = (b, kv, group, t)
    m, l, acc = jax.lax.fori_loop(
        0, tiles, one_tile,
        (jnp.full((*lead, 1), _NEG, jnp.float32),
         jnp.zeros((*lead, 1), jnp.float32),
         jnp.zeros((*lead, value_group * dv), jnp.float32)))
    out = acc / jnp.where(l == 0.0, 1.0, l)
    return out.transpose(0, 3, 1, 2, 4).reshape(
        b, t, heads, value_group * dv).astype(q.dtype)


def ring_blocks_for(window: int, block_size: int) -> int:
    """Blocks in a decode slot's ring of a window layer: the window and one
    more, so that the block being written never holds a key the window
    still needs."""
    return -(-window // block_size) + 1


def ring_slot_state(ring: int, knob: str):
    """``paged_slot_state_for``'s answer for a config whose window layers
    keep a ring of ``ring`` blocks a decode slot (None for 0: no window
    layer)."""
    if not ring:
        return None
    return {"entries": ring, "knob": knob,
            "what": "sliding-window layers keep their keys and values "
                    "in a ring a decode slot"}


def ring_kv_live_bytes(live, ring_rows: int, per_token: dict) -> dict:
    """Bytes of keys and values a decode step reads, by kind of layer, for
    busy rows of the lengths ``live``: a global layer every token of a
    sequence, a window layer what the slot's ring of ``ring_rows`` rows
    holds; ``per_token``: bytes a token keeps in each kind."""
    import numpy as np

    return {"global": int(live.sum()) * per_token["global"],
            "window": int(np.minimum(live, ring_rows).sum())
            * per_token["window"]}


def ring_gqa(q, k, v, pos, paging, table, k_pool, v_pool, index, label,
             sink=None, work=None, window: int = 0, value_group: int = 1):
    """:func:`paged_gqa` for a layer that sees the last ``window`` keys
    (the query's own among them), in the slot's ring ``table [B, ring]``:
    the table's last entries, whose rows a position takes by arithmetic
    (``ops/hybrid_decode_attention.py``). A whole prompt
    (``paging["prefill"]``) attends over its own keys; a decode step on a
    TPU runs the paged kernel; a prompt's later chunk, and every step where
    no TPU is, gathers the slot's ring and the step's own rows and takes
    the masked XLA path. What a head is before it comes here (normed,
    rotated or neither) is the caller's, as ``label`` is; ``value_group``:
    :func:`value_groups`."""
    from deepspeed_tpu.ops.attention import (record_dispatch,
                                             use_decode_kernel)
    from deepspeed_tpu.ops.hybrid_decode_attention import (
        decode_attention_hybrid, ring_positions)

    b, t = q.shape[:2]
    kv, bs, ring = k.shape[2], k_pool.shape[2], table.shape[-1]
    lengths, num_valid = paging["lengths"], paging["num_valid"]
    # of this step's rows the ring keeps the last (ring - 1) blocks'
    # worth: enough for the window, and never two rows on one place
    kept = pos >= (lengths + num_valid)[:, None] - (ring - 1) * bs
    real = (jnp.arange(t)[None] < num_valid[:, None]) & kept
    blk = jnp.where(real, jnp.take_along_axis(
        table, (pos // bs) % ring, axis=1), 0)
    off = pos % bs

    def write():
        return (k_pool.at[index, blk, off].set(k.reshape(b, t, -1)),
                v_pool.at[index, blk, off].set(v.reshape(b, t, -1)))

    def gathered(pool, width):
        """The ring's blocks of this layer, as rows in table order."""
        return pool[index, table].reshape(b, -1, kv, width)

    if paging.get("prefill"):
        record_dispatch(f"{label}_prefill_xla")
        k_pool, v_pool = write()
        y = causal_gqa(q, k, value_groups(v, value_group), window, sink)
    elif t == 1 and use_decode_kernel():
        record_dispatch(f"{label}_decode_kernel")
        k_pool, v_pool = write()
        with jax.named_scope("attn._hybrid_kv_attend"):
            y = decode_attention_hybrid(
                q, k_pool, v_pool, table, lengths, index, kv_heads=kv,
                window=window, ring=True, sink=sink, work=work,
                value_group=value_group)
    else:
        record_dispatch(f"{label}_cached_xla")
        # the ring as it stood BEFORE this step's rows, then the rows
        # themselves: a chunk's own writes would land on keys its
        # first queries still need
        held = ring_positions(lengths, ring * bs)
        y = masked_gqa(
            q, jnp.concatenate([gathered(k_pool, k.shape[-1]), k], 1),
            value_groups(jnp.concatenate(
                [gathered(v_pool, v.shape[-1]), v], 1), value_group),
            pos, jnp.concatenate([held, pos], 1),
            jnp.concatenate([held >= 0, jnp.arange(t)[None]
                             < num_valid[:, None]], 1), window, sink)
        k_pool, v_pool = write()
    return y, k_pool, v_pool


class LayerNorm(nn.Module):
    """Layer norm with a learned weight and bias, float32 statistics:
    :class:`RMSNorm`'s arguments, for a shell whose ``norm_class`` it is."""

    eps: float = 1e-5
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x):
        width = x.shape[-1]
        scale = self.param("scale", nn.initializers.ones, (width,),
                           jnp.float32)
        bias = self.param("bias", nn.initializers.zeros, (width,),
                          jnp.float32)
        x32 = x.astype(jnp.float32)
        x32 = x32 - jnp.mean(x32, axis=-1, keepdims=True)
        x32 = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1,
                                           keepdims=True) + self.eps)
        return (x32 * scale + bias).astype(self.dtype)


def last_rows(x, at):
    """``x [B, T, ..]`` at each row's position ``at [B]``: ``[B, 1, ..]``."""
    index = at.reshape(-1, *(1,) * (x.ndim - 1))
    return jnp.take_along_axis(x, index, axis=1)


def _last_rows(x, valid, pools, paging):
    """The shell's cut (:meth:`PagedDecoder.rows_from`): the stream and the
    validity of each sequence's last real row, and in ``pools`` where that
    row lies (``row_at [B]``; an idle row's is 0) and which tokens the
    layers before ran (``self_valid [B, T]``)."""
    at = jnp.maximum(paging["num_valid"] - 1, 0)
    return (last_rows(x, at), last_rows(valid, at),
            {**pools, "row_at": at, "self_valid": valid})
