"""ZeRO-Inference: serve models larger than device memory.

Capability parity with the reference's ZeRO-Inference
(``docs/_posts/2022-09-10-zero-inference.md:52``: OPT-30B served from CPU
offload at 43 tok/s; mechanism ``runtime/zero/partition_parameters.py:537``
— stage-3 parameter offload composed with the inference forward),
re-designed TPU-first:

- The reference fetches each module's partitioned params via allgather
  hooks before its ``forward``. Here the canonical decoder's **stacked
  block params stay host- or NVMe-resident as one ``[L, ...]`` tree** and
  stream through TWO device staging rows: ``jax.device_put`` of layer
  ``l+1`` is issued (async) while layer ``l``'s compiled program runs, so
  H2D rides under compute exactly like the training Infinity tier
  (``runtime/zero/infinity.py``).
- Per-layer programs are jitted ONCE and reused for every layer: a
  decode-config :class:`~deepspeed_tpu.models.gpt2.Block` apply with a
  flax ``cache`` collection. The KV cache (the true serving working set)
  lives on device for all layers; parameters — the part that does NOT fit
  — never have more than two layers resident.
- The regime is H2D-bandwidth-bound (one full model transfer per
  generated token batch), so the at-rest dtype is the first-order perf
  knob: ``dtype=bf16`` halves traffic vs fp32 and ``dtype=int8`` quarters
  it (weights stored as symmetric grouped int8 + scales, dequantized
  inside the per-layer program — the reference pairs ZeRO-Inference with
  the same weight-only quantization).
- NVMe tier: the stacked tree is written once as ``.npy`` files under
  ``offload_param.nvme_path`` and re-opened **memmapped**; a row fetch
  slices one layer from the maps, touching only that layer's pages.

The engine serves the canonical fused-decoder family (GPT-2/OPT/BLOOM/
GPT-J/NeoX weights through ``GPT2LMHeadModel`` with ``scan_layers=True``)
— the same family the training tier streams.
"""

import dataclasses
from collections import deque
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.runtime.config import DeepSpeedConfigError
from deepspeed_tpu.utils.logging import log_dist, logger
from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer


def wants_zero_inference(config) -> bool:
    """True when the inference config's ``zero`` section (config object or
    raw section dict) selects stage-3 parameter offload — the reference's
    ZeRO-Inference switch."""
    if config is None:
        return False
    z = (config if isinstance(config, dict)
         else config.zero) or {}
    if int(z.get("stage", 0)) != 3:
        return False
    off = z.get("offload_param") or {}
    if z.get("cpu_offload_param"):  # legacy spelling
        return True
    return str(off.get("device", "none")) in ("cpu", "nvme")


def host_init_params(model, seed: int = 0):
    """``model.init`` on the HOST backend. The whole premise of this tier
    is that the model does not fit (or barely fits) on the device, so
    materializing a full replica there — and paying the host link twice to
    bring it back at rest — is both an OOM hazard and wasted transfer. Uses
    the default device when no CPU backend is registered."""
    import contextlib

    try:
        cpu = jax.local_devices(backend="cpu")[0]
    except RuntimeError:
        cpu = None
    with (jax.default_device(cpu) if cpu is not None
          else contextlib.nullcontext()):
        return model.init(jax.random.PRNGKey(seed),
                          jnp.zeros((1, 8), jnp.int32))


def _np_quantize_rows(stack: np.ndarray, groups: int):
    """Symmetric grouped int8 over each layer row of a stacked ``[L, ...]``
    leaf (numpy mirror of :func:`ops.quantizer.quantize` semantics, applied
    per layer so a row dequantizes independently on device)."""
    L = stack.shape[0]
    flat = stack.reshape(L, -1).astype(np.float32)
    n = flat.shape[1]
    g = max(1, min(groups, n))
    while n % g:
        g -= 1
    grouped = flat.reshape(L, g, n // g)
    scale = np.abs(grouped).max(axis=2) / 127.0
    scale = np.where(scale == 0, 1.0, scale)
    q = np.clip(np.round(grouped / scale[:, :, None]), -128, 127)
    return (q.astype(np.int8).reshape(stack.shape),
            scale.astype(np.float32), g)


class ZeroInferenceEngine:
    """Offload-streamed serving engine (reference ZeRO-Inference).

    ``offload_param.buffer_size`` (when set) is the enforced device
    staging budget for block parameters: one layer's weights must fit in
    it (the engine refuses configurations where they do not), and a
    budget affording k rows prefetches k layers ahead — in-flight rows
    never exceed ``buffer_size // row_bytes`` (floor 2, cap ``n_layer``),
    so device block-param residency stays within the declared budget.
    """

    def __init__(self, model, config: Optional[DeepSpeedInferenceConfig] = None,
                 params=None, mesh=None, seed: int = 0, **kwargs):
        if config is None:
            config = DeepSpeedInferenceConfig(**kwargs)
        elif isinstance(config, dict):
            config = DeepSpeedInferenceConfig(**{**config, **kwargs})
        elif kwargs:
            merged = {**config.model_dump(exclude_unset=True), **kwargs}
            config = DeepSpeedInferenceConfig(**merged)
        self._config = config
        if mesh is not None or int(config.tensor_parallel.tp_size) > 1:
            raise DeepSpeedConfigError(
                "ZeRO-Inference is the single-device huge-model tier; with "
                "multiple chips use tensor_parallel sharding instead "
                "(init_inference without the zero section)")

        # unwrap training wrappers
        if hasattr(model, "model") and hasattr(model.model, "apply"):
            model = model.model
        from deepspeed_tpu.models.gpt2 import GPT2LMHeadModel

        cfgm = getattr(model, "config", None)
        if not isinstance(model, GPT2LMHeadModel) or cfgm is None \
                or not getattr(cfgm, "scan_layers", False):
            raise DeepSpeedConfigError(
                "ZeRO-Inference streams the scanned canonical decoder "
                "family (GPT2LMHeadModel with scan_layers=True — serves "
                "GPT-2/OPT/BLOOM/GPT-J/NeoX weights); other models fit on "
                "device or use tensor parallelism")
        if getattr(cfgm, "attention_windows", None) is not None:
            raise DeepSpeedConfigError(
                "ZeRO-Inference shares one compiled block program across "
                "layers; per-layer attention_windows need the device engine")
        self.module = model
        self.model_config = cfgm
        self._device = jax.devices()[0]
        self._timer = SynchronizedWallClockTimer()
        self._model_times = []
        # telemetry: the per-layer programs compile once and stream every
        # layer through them — a retrace here multiplies by n_layer, which
        # is exactly what the compile watchdog exists to catch
        from deepspeed_tpu.telemetry import Telemetry

        self.telemetry = Telemetry(config.telemetry, name="zero_inference")
        # resilience: hang watchdog on request progress (a wedged layer
        # stream stalls the per-token loop exactly like a training hang)
        from deepspeed_tpu.runtime.resilience import Resilience

        self.resilience = Resilience(config.resilience,
                                     telemetry=self.telemetry,
                                     name="zero_inference", serving=True)
        self._request_count = 0
        self.model_profile_enabled = False

        z = config.zero or {}
        off: Dict[str, Any] = dict(z.get("offload_param") or {})
        if z.get("cpu_offload_param") and not off:
            off = {"device": "cpu"}
        self._nvme = str(off.get("device")) == "nvme"
        if self._nvme and not off.get("nvme_path"):
            raise DeepSpeedConfigError(
                "offload_param.device=nvme requires nvme_path")

        # at-rest dtype: bf16 default (half the H2D bytes of fp32);
        # int8 stores {q, scale} and dequantizes inside the layer program
        self._dtype = (jnp.bfloat16 if config.dtype == jnp.int8
                       else config.dtype)
        self._int8 = config.dtype == jnp.int8
        self._q_groups = max(1, int(config.quant.weight.q_groups))

        # ---- host-resident parameter tree (canonical layout) ----
        from deepspeed_tpu.inference.engine import (resolve_checkpoint_params,
                                                    save_mp_checkpoint,
                                                    warn_inert_options)

        warn_inert_options(config)
        if params is None and config.checkpoint is not None:
            params = resolve_checkpoint_params(config.checkpoint,
                                               config.base_dir)
        if params is None:
            params = host_init_params(model, seed)
        if config.save_mp_checkpoint_path:
            # the resolved host tree in the model's native dtype — the
            # same fast-reload cache the device tier writes
            save_mp_checkpoint(config.save_mp_checkpoint_path, params)
        self._off = off
        self._install_params(params)
        log_dist(
            f"ZeroInferenceEngine: {self.n_layer} streamed layers "
            f"({'nvme' if self._nvme else 'host'}-resident, "
            f"{'int8' if self._int8 else np.dtype(self._dtype).name} at "
            f"rest, {self._row_bytes / 1e6:.2f} MB/layer); device keeps "
            f"embeddings/head + {self._prefetch_depth()} layer buffers + "
            "KV cache", ranks=[0])

    def _install_params(self, params):
        """(Re)build the at-rest stores from a raw param tree: canonical
        split, serving-dtype cast, optional int8 quantize, budget check,
        optional NVMe memmap, device-resident top.

        Every validation runs on LOCALS before any ``self`` state is
        touched — a refused reload (bad layout, over-budget checkpoint)
        must leave a live engine serving its previous model, not a
        half-installed hybrid."""
        from deepspeed_tpu.utils.pytree import unwrap_variables_dict

        off = self._off
        params = jax.device_get(unwrap_variables_dict(params))
        try:
            blocks = params["transformer"]["h"]["block"]
        except (KeyError, TypeError):
            raise DeepSpeedConfigError(
                "params do not carry the scanned canonical layout "
                "transformer/h/block — load them through the state-dict "
                "factory or model.init with scan_layers=True")
        n_layer = int(jax.tree_util.tree_leaves(blocks)[0].shape[0])
        top = {k: v for k, v in params.items() if k != "transformer"}

        def to_rest(a):
            # pure host cast: jnp dtypes (incl. bfloat16) are ml_dtypes
            # numpy scalar types, so no device round trip is needed — a
            # jnp.asarray here would stream every leaf through the
            # accelerator just to change its dtype
            a = np.asarray(a)
            if np.issubdtype(a.dtype, np.floating) or a.dtype == jnp.bfloat16:
                return np.ascontiguousarray(a.astype(self._dtype))
            return a

        blocks = jax.tree_util.tree_map(to_rest, blocks)
        top = jax.tree_util.tree_map(to_rest, top)
        # both halves counted at the serving (at-rest) dtype
        total_bytes = sum(
            l.nbytes for l in jax.tree_util.tree_leaves(blocks)) + sum(
            l.nbytes for l in jax.tree_util.tree_leaves(top))

        q_group_of = None
        if self._int8:
            blocks, q_group_of = self._quantize_blocks(blocks)
        row_bytes = sum(
            leaf.nbytes // n_layer
            for leaf in jax.tree_util.tree_leaves(blocks))

        # ---- enforced staging budget ----
        budget = off.get("buffer_size")
        if budget is not None and row_bytes > int(budget):
            raise DeepSpeedConfigError(
                f"offload_param.buffer_size={budget} is below one "
                f"layer's serving weights ({row_bytes} bytes); raise it "
                "to at least one layer (the budget is the in-flight "
                "staging pool: k affordable rows prefetch k layers ahead)")

        store = None
        if self._nvme:
            blocks, store = self._memmap_blocks(blocks, off["nvme_path"])
        # top (embeddings/head/final-LN — O(vocab), not O(depth)) is the
        # persistent device-resident set, already in the serving dtype.
        # Placed BEFORE the commit: a device OOM here (e.g. a reloaded
        # checkpoint with a much larger vocab table) must not leave a
        # half-installed hybrid
        top_dev = jax.device_put(top, self._device)

        # ---- commit point: every fallible operation succeeded ----
        self.n_layer = n_layer
        self._row_bytes = row_bytes
        self.total_param_bytes = total_bytes
        self._budget = budget
        if q_group_of is not None:
            self._q_group_of = q_group_of
        self._blocks = blocks
        self._top_dev = top_dev
        self._compiled: Dict[Any, Any] = {}
        if self._nvme:
            # a reload supersedes the previous on-disk store: unlink it
            # last (POSIX keeps the old maps' pages alive until the numpy
            # memmaps above are garbage-collected with self._blocks) —
            # otherwise every load_checkpoint leaks a full model copy
            if getattr(self, "_nvme_store", None):
                import shutil

                shutil.rmtree(self._nvme_store, ignore_errors=True)
            self._nvme_store = store

    def load_checkpoint(self, load_dir, tag=None):
        """Reload at-rest parameters from a training checkpoint (same
        surface as ``InferenceEngine.load_checkpoint``, reference
        ``engine.py:269``): the module state re-enters the host/NVMe
        pipeline; compiled per-layer programs are rebuilt."""
        from deepspeed_tpu.inference.engine import load_module_params

        self._install_params(load_module_params(load_dir, tag))

    # ------------------------------------------------------------------
    def _quantize_blocks(self, blocks):
        """Weight-only int8 at rest: matmul leaves (ndim>=3 stacked) become
        ``{"q", "scale"}``; vectors (LN/bias) stay in the serving dtype.
        Pure — returns ``(blocks, group_map)`` so a failed install never
        half-updates the engine."""
        group_of = {}

        def q(path, leaf):
            a = np.asarray(leaf)
            if a.ndim >= 3 and (a.dtype == jnp.bfloat16
                                or np.issubdtype(a.dtype, np.floating)):
                qv, scale, g = _np_quantize_rows(a, self._q_groups)
                group_of[jax.tree_util.keystr(path)] = g
                return {"q": qv, "scale": scale}
            return a

        return jax.tree_util.tree_map_with_path(q, blocks), group_of

    @staticmethod
    def _memmap_blocks(blocks, nvme_path):
        """Write the stacked tree once under ``nvme_path`` and re-open it
        memmapped — a row fetch then reads one layer's pages from disk.
        Each engine writes into its own fresh subdirectory: np.save would
        otherwise truncate a sibling engine's live maps in place (SIGBUS /
        silent corruption on its next row fetch)."""
        import os
        import tempfile

        os.makedirs(nvme_path, exist_ok=True)
        store = tempfile.mkdtemp(prefix="zinf_", dir=nvme_path)

        def mm(path, leaf):
            a = np.asarray(leaf)
            fname = os.path.join(
                store,
                "zinf_" + jax.tree_util.keystr(path).replace("'", "")
                .replace("[", "_").replace("]", "") + ".npy")
            if a.dtype == jnp.bfloat16:  # npy can't tag bf16: store u16 view
                np.save(fname, a.view(np.uint16))
                return np.load(fname, mmap_mode="r").view(jnp.bfloat16)
            np.save(fname, a)
            return np.load(fname, mmap_mode="r")

        return jax.tree_util.tree_map_with_path(mm, blocks), store

    # ------------------------------------------------------------------
    def _row(self, l: int):
        return jax.tree_util.tree_map(lambda a: a[l], self._blocks)

    def _fetch_row(self, l: int):
        """Layer ``l``'s at-rest weights on device — async, so issuing the
        fetch for ``l+1`` overlaps layer ``l``'s program."""
        # memmap slices must be materialized (device_put may read the host
        # buffer after return; a mmap page could also be evicted mid-copy)
        row = jax.tree_util.tree_map(
            np.ascontiguousarray if self._nvme else (lambda a: a),
            self._row(l))
        return jax.device_put(row, self._device)

    @property
    def streamed_param_bytes(self) -> int:
        """Bytes crossing H2D per full layer sweep (one decode step /
        prefill): the at-rest block rows; the device-resident top never
        re-transfers."""
        return self._row_bytes * self.n_layer

    def device_param_bytes(self) -> int:
        """Bytes of parameters the device holds at steady state: the
        persistent top tree + the in-flight staged rows (the budget proof
        the serving tests pin against ``total_param_bytes``)."""
        top = sum(l.nbytes
                  for l in jax.tree_util.tree_leaves(self._top_dev))
        return top + self._prefetch_depth() * self._row_bytes

    # ------------------------------------------------------------------
    def _fns(self, B: int, T: int, padded: bool = False):
        """Per-layer compiled programs, shared by all layers (one compile
        per (batch, seq, padded) shape). ``padded`` variants thread the
        LEFT-padding attention mask through prefill (the Block's padded
        decode cache tracks each row's pad prefix from there on) and give
        the embedding per-row positions."""
        key = (B, T, padded)
        if key in self._compiled:
            return self._compiled[key]
        import flax.linen as nn

        from deepspeed_tpu.models.decode_utils import row_positions
        from deepspeed_tpu.models.gpt2 import Block

        cfg = self.model_config
        cfg_fwd = dataclasses.replace(cfg, dropout=0.0, dtype=self._dtype)
        dcfg = cfg.for_decode(padded=padded)
        dcfg = dataclasses.replace(dcfg, dtype=self._dtype)
        block_fwd = Block(cfg_fwd)
        block_dec = Block(dcfg)

        dq = self._dequant_row if self._int8 else (lambda bp: bp)

        def embed(top, ids, pos0):
            x = jnp.take(top["wte"], ids, axis=0).astype(self._dtype)
            if cfg.position_embedding == "learned":
                pos = jax.lax.dynamic_slice(
                    top["wpe"], (pos0 + cfg.position_offset, 0),
                    (T, cfg.n_embd))
                x = x + pos[None].astype(self._dtype)
            if cfg.embedding_layernorm:
                x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                                 dtype=self._dtype).apply(
                    {"params": top["emb_ln"]}, x)
            return x

        def embed_rows(top, ids, pos_ids):
            """Per-row positions ([B, T], 0 at each row's first real
            token) — the padded prefill/decode embedding."""
            x = jnp.take(top["wte"], ids, axis=0).astype(self._dtype)
            if cfg.position_embedding == "learned":
                pos = jnp.take(top["wpe"],
                               pos_ids + cfg.position_offset, axis=0)
                x = x + pos.astype(self._dtype)
            if cfg.embedding_layernorm:
                x = nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                                 dtype=self._dtype).apply(
                    {"params": top["emb_ln"]}, x)
            return x

        def lnf(top, h):
            return nn.LayerNorm(epsilon=cfg.layer_norm_epsilon,
                                dtype=self._dtype).apply(
                {"params": top["ln_f"]}, h)

        def logits_all(top, h):
            x = lnf(top, h)
            w = top["wte"] if cfg.tied_head else top["lm_head"]
            out = x.astype(jnp.float32) @ w.astype(jnp.float32).T
            if cfg.lm_head_bias:
                out = out + top["lm_head_bias"].astype(jnp.float32)
            return out

        def logits_last(top, h):
            return logits_all(top, h[:, -1:, :])[:, 0, :]

        def prefill_block(bp, x, mask):
            kw = {"attention_mask": mask} if padded else {}
            y, vars_ = block_dec.apply({"params": dq(bp)}, x, True,
                                       mutable=["cache"], **kw)
            return y, vars_["cache"]

        def decode_block(bp, cache, x):
            y, vars_ = block_dec.apply({"params": dq(bp), "cache": cache},
                                       x, True, mutable=["cache"])
            return y, vars_["cache"]

        def plain_block(bp, x):
            return block_fwd.apply({"params": dq(bp)}, x, True)

        tag = f"[B={B},T={T}{',padded' if padded else ''}]"
        fns = {
            "embed": jax.jit(embed),
            "embed_rows": jax.jit(embed_rows),
            "row_positions": jax.jit(row_positions),
            "logits_all": jax.jit(logits_all),
            "logits_last": jax.jit(logits_last),
            "prefill_block": self.telemetry.watch_jit(
                jax.jit(prefill_block), f"zero_infer.prefill_block{tag}"),
            "decode_block": self.telemetry.watch_jit(
                jax.jit(decode_block, donate_argnums=(1,)),
                f"zero_infer.decode_block{tag}"),
            "plain_block": self.telemetry.watch_jit(
                jax.jit(plain_block), f"zero_infer.plain_block{tag}"),
        }
        self._compiled[key] = fns
        return fns

    def _dequant_row(self, bp):
        """In-program dequant of an int8 row (traced inside the layer jit:
        the int8 payload is what crosses PCIe/DMA, fp never does)."""
        def dq(path, leaf):
            if isinstance(leaf, dict) and set(leaf) == {"q", "scale"}:
                g = self._q_group_of[jax.tree_util.keystr(path)]
                q = leaf["q"].astype(jnp.float32).reshape(g, -1)
                w = q * leaf["scale"][:, None]
                return w.reshape(leaf["q"].shape).astype(self._dtype)
            return leaf

        # tree_map treats the {"q","scale"} dicts as leaves via is_leaf
        return jax.tree_util.tree_map_with_path(
            dq, bp, is_leaf=lambda x: isinstance(x, dict)
            and set(x) == {"q", "scale"})

    def _sampler(self, do_sample: bool, top_k: int, top_p: float):
        key = ("sample", do_sample, top_k, top_p)
        if key in self._compiled:
            return self._compiled[key]
        from deepspeed_tpu.inference.engine import sample_logits

        fn = jax.jit(lambda logits, rng, temperature: sample_logits(
            logits, rng, temperature, do_sample, top_k, top_p))
        self._compiled[key] = fn
        return fn

    # ------------------------------------------------------------------
    def _prefetch_depth(self) -> int:
        """Rows in flight at once. Two (double buffering) is the floor;
        when ``buffer_size`` affords more, a deeper pipeline absorbs
        host-side fetch jitter (NVMe page faults, allocator stalls) that
        a 2-deep pipeline surfaces as device idle time. Capped at the
        layer count — deeper would just be the whole model resident."""
        if self._budget is None:
            return 2
        return max(2, min(self.n_layer, int(self._budget) // max(
            1, self._row_bytes)))

    def _stream(self, x, fn_of_layer):
        """Run ``x`` through all layers; row fetches are issued ahead so
        queued H2D copies ride under the running layer programs
        (``jax.device_put`` is async). In-flight rows — the popped ``cur``
        plus the fifo — never exceed ``_prefetch_depth``, so device
        residency matches ``device_param_bytes()``'s accounting."""
        L = self.n_layer
        depth = self._prefetch_depth()
        next_fetch = min(depth - 1, L)
        fifo = deque(self._fetch_row(l) for l in range(next_fetch))
        for l in range(L):
            cur = fifo.popleft()  # row l (the fifo is never empty here:
            # it is seeded with depth-1 >= 1 rows and refilled each step)
            x = fn_of_layer(l, cur, x)
            if next_fetch < L:
                fifo.append(self._fetch_row(next_fetch))
                next_fetch += 1
        return x

    def forward(self, input_ids, **kwargs):
        """Full-sequence logits, parameters streamed (reference
        ``engine.py:496`` surface on the ZeRO-Inference tier)."""
        ids = jnp.asarray(input_ids)
        if ids.ndim == 1:
            ids = ids[None]
        B, T = ids.shape
        fns = self._fns(B, T)
        t = self._timer("model_forward")
        t.start()
        x = fns["embed"](self._top_dev, jax.device_put(ids, self._device),
                         jnp.zeros((), jnp.int32))
        x = self._stream(x, lambda l, row, h: fns["plain_block"](row, h))
        out = jax.block_until_ready(fns["logits_all"](self._top_dev, x))
        t.stop()
        self._record_model_time("forward", t.elapsed(reset=True))
        return out

    __call__ = forward

    def generate(self, input_ids, max_new_tokens: Optional[int] = None,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 0.0, eos_token_id: int = -1,
                 attention_mask=None, rng=None, **kwargs):
        """Streamed autoregressive generation: each decode step moves every
        layer's at-rest weights across H2D once — tokens/s is bounded by
        ``bandwidth / model_bytes``, which is why the at-rest dtype (bf16 /
        int8) is the headline knob. ``attention_mask`` ([B, T], 0 = LEFT
        padding) batches prompts of unequal length, same contract as the
        device engine. Returns prompt + new tokens, HF-style."""
        # resilience bracket — see InferenceEngine.generate
        self.resilience.serving_request_begin()
        try:
            return self._generate_impl(
                input_ids, max_new_tokens=max_new_tokens,
                do_sample=do_sample, temperature=temperature, top_k=top_k,
                top_p=top_p, eos_token_id=eos_token_id,
                attention_mask=attention_mask, rng=rng, **kwargs)
        except BaseException:
            self.resilience.serving_request_abandon()
            raise

    def _generate_impl(self, input_ids, max_new_tokens: Optional[int] = None,
                       do_sample: bool = False, temperature: float = 1.0,
                       top_k: int = 0, top_p: float = 0.0,
                       eos_token_id: int = -1, attention_mask=None, rng=None,
                       **kwargs):
        ids = jnp.asarray(input_ids)
        if ids.ndim == 1:
            ids = ids[None]
        B, T = ids.shape
        if attention_mask is not None:
            from deepspeed_tpu.models.decode_utils import (
                decode_positions, pad_lengths, validate_left_padded_mask)

            attention_mask = validate_left_padded_mask(ids, attention_mask)
        padded = attention_mask is not None
        if padded:
            # per-row padded-prefix lengths drive the decode positions
            pad_lens = pad_lengths(attention_mask, T)
        cfg = self.model_config
        limit = cfg.n_positions
        if max_new_tokens is None:
            max_new_tokens = min(self._config.max_out_tokens, limit) - T
        if T + max_new_tokens > limit:
            raise ValueError(
                f"prompt ({T}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"model window {limit}")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        if rng is None:
            rng = jax.random.PRNGKey(np.random.default_rng().integers(2**31))
        sample = self._sampler(bool(do_sample), int(top_k), float(top_p))
        temp = jnp.asarray(temperature, jnp.float32)

        t = self._timer("generate")
        t.start()
        pfns = self._fns(B, T, padded)
        dfns = self._fns(B, 1, padded)
        caches = [None] * self.n_layer
        ids_dev = jax.device_put(ids, self._device)
        mask_dev = (jax.device_put(attention_mask, self._device)
                    if padded else None)

        def prefill(l, row, h):
            h, caches[l] = pfns["prefill_block"](row, h, mask_dev)
            return h

        if padded:
            x = pfns["embed_rows"](self._top_dev, ids_dev,
                                   pfns["row_positions"](mask_dev))
        else:
            x = pfns["embed"](self._top_dev, ids_dev,
                              jnp.zeros((), jnp.int32))
        x = self._stream(x, prefill)
        rng, sub = jax.random.split(rng)
        token = sample(pfns["logits_last"](self._top_dev, x), sub, temp)
        tokens = [np.asarray(token)]
        done = tokens[0] == eos_token_id

        def dec(l, row, h):
            h, caches[l] = dfns["decode_block"](row, caches[l], h)
            return h

        for step in range(max_new_tokens - 1):
            if done.all():
                tokens.append(np.full((B,), eos_token_id, tokens[0].dtype))
                continue
            if padded:
                # row r's absolute position is (T + step) minus its pad
                pos_ids = decode_positions(T + step, 1, pad_lens)
                x = dfns["embed_rows"](self._top_dev, token[:, None],
                                       pos_ids)
            else:
                x = dfns["embed"](self._top_dev, token[:, None],
                                  jnp.asarray(T + step, jnp.int32))
            x = self._stream(x, dec)
            rng, sub = jax.random.split(rng)
            token = sample(dfns["logits_last"](self._top_dev, x), sub, temp)
            nxt = np.asarray(token)
            nxt = np.where(done, eos_token_id, nxt)
            done = done | (nxt == eos_token_id)
            tokens.append(nxt)
            token = jnp.asarray(nxt)
        t.stop()
        self._record_model_time("generate", t.elapsed(reset=True))
        # request boundary: the per-token host loop above already syncs
        # (np.asarray on each sampled token), so the sample is passive
        self._request_count += 1
        self.telemetry.on_step_boundary(self._request_count, samples=int(B))
        self.resilience.serving_heartbeat(self._request_count)
        return np.concatenate(
            [np.asarray(ids)] + [tk[:, None] for tk in tokens], axis=1)

    # ------------------------------------------------------------------
    def _record_model_time(self, name, seconds):
        # same contract as InferenceEngine._record_model_time: buffer for
        # model_times() AND mirror into the telemetry stream
        self._model_times.append(seconds)
        self.telemetry.emit("model_time", name, step=self._request_count,
                            ms=round(1e3 * seconds, 4))

    def model_times(self):
        times = self._model_times
        self._model_times = []
        return times

    def profile_model_time(self, use_cuda_events=None):
        if use_cuda_events is not None:
            import warnings

            warnings.warn(
                "profile_model_time(use_cuda_events=...) is CUDA-era and "
                "ignored on this backend", DeprecationWarning, stacklevel=2)
        self.model_profile_enabled = True

    def destroy(self):
        """Release the per-shape compiled programs and close telemetry
        (stopping any open trace window)."""
        self._compiled.clear()
        self.resilience.close()
        self.telemetry.close()

    def eval(self):
        return self

    def train(self, mode=False):
        return self
