"""Inference engine.

Capability parity with the reference ``InferenceEngine``
(``deepspeed/inference/engine.py:31``), re-designed TPU-first:

- TP group creation (``engine.py:178``) → a ``model`` mesh axis; weights are
  laid out by an injection policy (``module_inject``) as ``PartitionSpec``s
  and GSPMD inserts the row-parallel psum the reference issues by hand.
- dtype conversion (``engine.py:438``) → params cast once at load.
- kernel injection (``_apply_injection_policy``, ``engine.py:326``) → the
  model's attention already routes through the Pallas kernels; the policy
  here only controls sharding.
- CUDA-graph capture/replay (``engine.py:455,474``) → jit compile cache:
  prefill and decode are two compiled programs keyed by shape.
- KV-cache workspace (``csrc/.../inference_context.h``) → explicit cache
  arrays in a flax ``cache`` collection, sharded over the ``model`` axis.
- ``generate`` (``engine.py:524``) → one jitted prefill + ``lax.scan`` over
  decode steps with greedy/temperature/top-k/top-p (nucleus) sampling.
"""

import dataclasses
import os
from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig
from deepspeed_tpu.module_inject.policies import get_tp_policy
from deepspeed_tpu.parallel.topology import (AXIS_DATA, AXIS_MODEL,
                                             MeshTopology, get_topology,
                                             set_topology)
from deepspeed_tpu.telemetry.manager import constructor_bracket
from deepspeed_tpu.utils.logging import log_dist
from deepspeed_tpu.utils.timer import SynchronizedWallClockTimer


def _is_floating(x) -> bool:
    return hasattr(x, "dtype") and jnp.issubdtype(x.dtype, jnp.floating)


def sample_logits(logits, rng, temperature, do_sample: bool, top_k: int,
                  top_p: float):
    """Greedy/temperature/top-k/top-p (nucleus) next-token sampling —
    shared by the device engine and the ZeRO-Inference tier so the two
    cannot drift. ``do_sample``/``top_k``/``top_p`` must be Python-static
    (they select the traced program); ``temperature`` may be traced.
    Nucleus keeps the smallest prefix of the sorted distribution whose
    mass reaches ``top_p`` (the first token past the threshold stays,
    HF-style)."""
    logits = logits.astype(jnp.float32)
    if not do_sample:
        return jnp.argmax(logits, axis=-1)
    logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k > 0:
        kth = jax.lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p > 0.0:
        sorted_logits = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = cum - probs < top_p
        cutoff = jnp.min(jnp.where(keep, sorted_logits, jnp.inf),
                         axis=-1, keepdims=True)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1)


def resolve_checkpoint_params(checkpoint, base_dir=""):
    """Params for an inference engine's ``checkpoint=`` kwarg (reference
    ``engine.py:269`` loads it at construction; dropping it silently
    would serve random weights for a call that names a real model).
    Accepts a checkpoint DIRECTORY — training ``save_checkpoint`` layout
    or a ``save_mp_checkpoint_path`` output — optionally joined onto
    ``base_dir`` when relative; anything else fails loudly with
    guidance. Shared by both serving tiers so they cannot drift."""

    from deepspeed_tpu.runtime.config import DeepSpeedConfigError

    if base_dir and isinstance(checkpoint, str) \
            and not os.path.isabs(checkpoint):
        checkpoint = os.path.join(base_dir, checkpoint)
    if isinstance(checkpoint, str) and os.path.isdir(checkpoint):
        return load_module_params(checkpoint)
    raise DeepSpeedConfigError(
        f"checkpoint= resolved to {checkpoint!r}, which is not a "
        "checkpoint DIRECTORY (training save_checkpoint layout or a "
        "save_mp_checkpoint_path output); for HF model names / "
        "sharded-index dirs / Megatron descriptors use "
        "deepspeed_tpu.inference.auto.from_pretrained")


def warn_inert_options(config):
    """Loudly name reference options that are accepted but have no
    TPU-side behavior (same contract as the training engine's inert
    activation-checkpointing knobs): the call keeps working, the user
    learns the knob does nothing here, nothing is silently dropped.
    Shared by both serving tiers."""
    inert = {
        "enable_cuda_graph": "XLA's jit compile cache supersedes "
                             "CUDA-graph capture",
        "triangular_masking": "each model owns its masking (causal "
                              "decoders mask causally regardless)",
        "set_empty_params": "flax init is deferred by construction; "
                            "pass checkpoint= or params=",
        "training_mp_size": "checkpoint loaders reshape TP degree "
                            "automatically",
        "return_tuple": "forward returns the logits array",
        "min_out_tokens": "no kernel workspace needs a floor here",
        "transposed_mode": "weight layouts are canonical",
        "moe": "MoE serving is selected by the model family "
               "(GPTMoE), not a config switch",
    }
    fields_set = config.model_fields_set or ()
    for name, why in inert.items():
        if name in fields_set and getattr(config, name) != \
                type(config).model_fields[name].get_default():
            # a value equal to the default (common in dumped reference
            # configs) is not worth a warning — only a knob someone
            # actually turned
            log_dist(f"inference config '{name}' has no effect on "
                     f"this backend: {why}", ranks=[0])


def save_mp_checkpoint(path, params_host):
    """Reference ``save_mp_checkpoint_path`` (inference config): write the
    dtype-CONVERTED weights so the next ``init_inference(checkpoint=path)``
    (or ``load_checkpoint``) skips source parsing and conversion. The
    reference writes per-mp-rank shard files; here rank 0 saves the full
    tree once in the training-checkpoint layout — resharding to any TP
    degree is a sharding annotation at load, not a data transform — and
    every rank barriers so a follow-up load never races the write."""

    import deepspeed_tpu.comm as dist
    from deepspeed_tpu.runtime.checkpoint_engine.checkpoint_engine import (
        ArrayCheckpointEngine)

    if dist.get_rank() == 0:
        tag = "inference"
        eng = ArrayCheckpointEngine()
        eng.save({"params": jax.device_get(params_host)},
                 os.path.join(path, tag, "module"))
        with open(os.path.join(path, "latest"), "w") as f:
            f.write(tag)
        log_dist(f"saved inference (mp) checkpoint to {path}", ranks=[0])
    if dist.get_world_size() > 1:
        dist.barrier()


def load_module_params(load_dir, tag=None):
    """Raw module param tree from a training checkpoint dir — the shared
    tag-resolution ('latest' file, ``global_step0`` fallback) and layout
    parsing both serving tiers load through (reference ``engine.py:269``)."""

    from deepspeed_tpu.runtime.checkpoint_engine.checkpoint_engine import (
        ArrayCheckpointEngine)

    eng = ArrayCheckpointEngine()
    if tag is None:
        latest = os.path.join(load_dir, "latest")
        tag = (open(latest).read().strip() if os.path.exists(latest)
               else "global_step0")
    state = eng.load(os.path.join(load_dir, str(tag), "module"))
    if isinstance(state, dict) and any("/" in k for k in state):
        from deepspeed_tpu.runtime.engine import _unflatten_by_paths

        return _unflatten_by_paths(state, "params/")
    return state["params"] if "params" in state else state


class InferenceEngine:
    """Wraps a flax LM for sharded, jitted generation.

    ``model`` is a flax module (e.g. :class:`GPT2LMHeadModel`) whose
    ``config`` dataclass has a ``for_decode()`` method (KV-cache variant),
    or a training wrapper exposing ``.model``/``.config`` (e.g.
    :class:`GPT2ForTraining`).
    """

    @constructor_bracket("inference_init", span="startup.inference_init")
    def __init__(self,
                 model,
                 config: Optional[DeepSpeedInferenceConfig] = None,
                 params=None,
                 example_input=None,
                 mesh: Optional[MeshTopology] = None,
                 seed: int = 0,
                 **kwargs):
        if config is None:
            config = DeepSpeedInferenceConfig(**kwargs)
        elif isinstance(config, dict):
            config = DeepSpeedInferenceConfig(**{**config, **kwargs})
        elif kwargs:  # built config + overrides: revalidate through pydantic
            merged = {**config.model_dump(exclude_unset=True), **kwargs}
            config = DeepSpeedInferenceConfig(**merged)
        self._config = config

        # unwrap training wrappers
        if hasattr(model, "model") and hasattr(model.model, "apply"):
            model = model.model
        self.module = model
        self.model_config = getattr(model, "config", None)

        # ---- TP mesh (reference _create_model_parallel_group, engine.py:178)
        tp = int(config.tensor_parallel.tp_size)
        if mesh is not None:
            self.topo = mesh if isinstance(mesh, MeshTopology) else MeshTopology(mesh=mesh)
        else:
            from deepspeed_tpu.parallel.topology import resolve_tp_topology

            self.topo = resolve_tp_topology(tp)
        self.mesh = self.topo.mesh
        self.mp_world_size = self.topo.get_model_parallel_world_size()

        # ---- params: adopt / load from checkpoint / init, then
        # dtype-convert + shard
        self._rng = jax.random.PRNGKey(seed)
        warn_inert_options(config)
        if params is None and config.checkpoint is not None:
            params = resolve_checkpoint_params(config.checkpoint,
                                               config.base_dir)
        if params is None:
            if example_input is None:
                example_input = jnp.zeros((1, 8), jnp.int32)
            params = model.init(self._rng, example_input)
        from deepspeed_tpu.utils.pytree import unwrap_variables_dict

        params = unwrap_variables_dict(params)
        self.policy = self._resolve_policy(config.injection_policy
                                           or config.injection_policy_tuple)
        params = self._convert_dtype(params)
        if config.save_mp_checkpoint_path:
            self._save_mp_checkpoint(config.save_mp_checkpoint_path, params)
        self.params, self.param_shardings = self._shard_params(params)

        self._quantized = config.dtype == jnp.int8
        if self._quantized:
            self.params, self._quant_meta = self._quantize_weights(self.params)

        self._timer = SynchronizedWallClockTimer()
        self._forward_fn = None
        self._forward_last_fn = None
        self._generate_cache: Dict[Any, Callable] = {}
        self._model_times = []
        self.model_profile_enabled = False
        # serving block (paged KV / continuous batching — consumed by
        # ServingEngine). Absent → None: this engine's compiled HLO and
        # generate() cache keying stay byte-identical (pinned in
        # tests/unit/test_serving.py); present → generate() pads prompt
        # lengths up to the serving bucket set before keying its cache
        self._serving_cfg = None
        # live tuned config (`tuning` block): serving knobs (prefill
        # chunk tokens, prompt buckets) fill in where the user's serving
        # dict left them unset, and the artifact's decode-kernel tile
        # choices install for this engine's lifetime (removed at
        # destroy). Fingerprint-verified loudly before anything applies.
        self._tuned_install = None
        serving_dict = dict(config.serving) if config.serving else None
        tuned_ops = {}
        if (config.tuning or {}).get("enabled"):
            from deepspeed_tpu.autotuning.artifact import (apply_section,
                                                           load_for_config,
                                                           ops_choices)

            artifact = load_for_config(config.tuning)
            if serving_dict is not None:
                serving_dict = apply_section(serving_dict, artifact,
                                             "serving")
                if (serving_dict.get("do_sample")
                        and "speculative" not in (config.serving or {})):
                    # a tuned speculation choice applies only to greedy
                    # serving (the accept oracle IS the greedy stream);
                    # filling it into a sampling config would fail the
                    # config validator at startup over a bench artifact
                    # the user never wrote
                    serving_dict.pop("speculative", None)
            tuned_ops = ops_choices(artifact)
        if serving_dict is not None:
            from deepspeed_tpu.serving.config import ServingConfig

            self._serving_cfg = ServingConfig(**serving_dict)
        # telemetry: serving-side compile watchdog / HLO cost / memory —
        # a generate-shape recompile storm is the serving analog of the
        # training engine's retrace blind spot
        from deepspeed_tpu.telemetry import Telemetry

        self.telemetry = Telemetry(config.telemetry, name="inference")
        # resilience: the hang watchdog covers serving too — a wedged
        # collective inside a generate step stalls request progress the
        # same way a training stall stops step boundaries
        from deepspeed_tpu.runtime.resilience import Resilience

        self.resilience = Resilience(config.resilience,
                                     telemetry=self.telemetry,
                                     name="inference", serving=True)
        self._request_count = 0
        if tuned_ops:
            # the LAST construction step (same ordering contract as the
            # training engine): tiles resolve at trace time, and an
            # install before any later-raising validation (ServingConfig,
            # Telemetry, Resilience) would leak process-wide with
            # destroy() forever unreachable
            from deepspeed_tpu.autotuning import runtime_tunables

            self._tuned_install = runtime_tunables.install(tuned_ops)
        log_dist(
            f"InferenceEngine: tp={self.mp_world_size} dtype={config.dtype} "
            f"kernel_inject={config.replace_with_kernel_inject}", ranks=[0])

    # ------------------------------------------------------------------
    @staticmethod
    def _resolve_policy(injection_policy):
        """Accept a policy name, a TPPolicy, or a reference-style dict of
        ``{segment_or_module_name: role_or_param_names}`` (the reference's
        ``injection_policy={Class: ('attn.c_proj',)}`` kwarg,
        ``inference/engine.py:326``)."""
        from deepspeed_tpu.module_inject.policies import ROW, TPPolicy

        if injection_policy is None:
            return get_tp_policy("auto")
        if isinstance(injection_policy, (tuple, list)):
            # reference injection_policy_tuple: a bare tuple naming the
            # row-parallel output params
            injection_policy = {"_tuple": tuple(injection_policy)}
        if isinstance(injection_policy, dict):
            rules = []
            for key, val in injection_policy.items():
                if isinstance(val, str):  # {"c_proj": "row"} role form
                    rules.append((str(key), val))
                else:  # reference form: values name the row-parallel outputs
                    names = (val,) if isinstance(val, str) else tuple(val)
                    for n in names:
                        rules.append((str(n).rsplit(".", 1)[-1], ROW))
            from deepspeed_tpu.module_inject.policies import AUTO_POLICY

            return TPPolicy("user", rules + AUTO_POLICY.rules)
        return get_tp_policy(injection_policy)

    def _convert_dtype(self, params):
        """Reference ``_convert_to_dtype`` (``inference/engine.py:438``)."""
        dtype = self._config.dtype
        if dtype == jnp.int8:  # handled by _quantize_weights
            return params
        return jax.tree_util.tree_map(
            lambda x: x.astype(dtype) if _is_floating(x) else x, params)

    def _shard_params(self, params):
        from deepspeed_tpu.module_inject.policies import \
            shard_params_with_policy

        return shard_params_with_policy(params, self.policy, self.mesh)

    def _quantize_weights(self, params):
        """Weight-only int8 groupwise quantization (reference
        ``GroupQuantizer``, ``module_inject/replace_module.py:140``). Matmul
        weights (ndim>=2) are stored int8 with per-group scales and
        dequantized at the top of the jitted step — int8 halves *at-rest*
        (host/HBM-resident) weight memory; peak in-step memory still sees the
        full-precision tree. Per-layer dequant inside the scanned block (and
        a Pallas int8 matmul) is the follow-up that makes peak memory
        one-layer-sized."""
        from deepspeed_tpu.ops.quantizer import quantize

        wq = self._config.quant.weight
        groups = max(1, int(wq.q_groups))
        symmetric = str(getattr(wq, "q_type", "symmetric")) != "asymmetric"
        flat, treedef = jax.tree_util.tree_flatten(params)
        # quantization is a pytree-wide transform; remember which leaves
        qflat, meta = [], []
        for leaf in flat:
            if _is_floating(leaf) and leaf.ndim >= 2:
                out = quantize(leaf.astype(jnp.float32), num_groups=groups,
                               num_bits=wq.num_bits, symmetric=symmetric)
                if symmetric:
                    q, scale = out
                    qflat.append({"q": q, "scale": scale})
                else:  # asymmetric carries the per-group zero point
                    q, scale, zp = out
                    qflat.append({"q": q, "scale": scale, "zp": zp})
                meta.append((True, leaf.dtype, leaf.shape))
            else:
                qflat.append(leaf)
                meta.append((False, None, None))
        return jax.tree_util.tree_unflatten(treedef, qflat), (treedef, meta)

    def _dequantize(self, params):
        from deepspeed_tpu.ops.quantizer import dequantize

        if not self._quantized:
            return params
        treedef, meta = self._quant_meta
        wq = self._config.quant.weight
        groups = max(1, int(wq.q_groups))
        is_q = lambda x: (isinstance(x, dict)
                          and set(x) in ({"q", "scale"}, {"q", "scale", "zp"}))
        flat = treedef.flatten_up_to(params)
        out = []
        for leaf, (was_q, dtype, shape) in zip(flat, meta):
            if was_q and is_q(leaf):
                w = dequantize(leaf["q"], leaf["scale"],
                               zero_point=leaf.get("zp"), num_groups=groups,
                               num_bits=wq.num_bits)
                out.append(w.reshape(shape).astype(dtype))
            else:
                out.append(leaf)
        return jax.tree_util.tree_unflatten(treedef, out)

    # ------------------------------------------------------------------
    def _decode_module(self, padded: bool = False):
        cfg = self.model_config
        if cfg is None or not hasattr(cfg, "for_decode"):
            raise ValueError(
                "model config must provide for_decode() for KV-cache generation")
        if padded:
            try:
                dcfg = cfg.for_decode(padded=True)
            except TypeError:
                raise ValueError(
                    "attention_mask generation (left-padded batches) needs "
                    "a model whose for_decode accepts padded=True — the "
                    "canonical decoder family (GPT2LMHeadModel) and Llama "
                    "support it; pad-free prompts work with every model"
                ) from None
            return type(self.module)(dcfg)
        return type(self.module)(cfg.for_decode())

    @staticmethod
    def _logits_of(out):
        """Models may return (logits, aux) — e.g. GPT-MoE's load-balance
        loss, a training artifact irrelevant at inference."""
        return out[0] if isinstance(out, tuple) else out

    def forward(self, input_ids, **kwargs):
        """Full (non-cached) forward — reference ``engine.py:496``."""
        if self._forward_fn is None:
            module = self.module

            def fwd(params, ids):
                return self._logits_of(module.apply(
                    {"params": self._dequantize(params)}, ids))

            self._forward_fn = self.telemetry.watch_jit(
                jax.jit(fwd), "inference.forward")
        t = self._timer("model_forward")
        t.start()
        out = jax.block_until_ready(self._forward_fn(self.params, input_ids))
        t.stop()
        self._record_model_time("forward", t.elapsed(reset=True))
        return out

    __call__ = forward

    def forward_last(self, input_ids):
        """Last-position logits only — the prefill a serving request
        actually needs (the next token depends on ``logits[:, -1]``
        alone). Slicing INSIDE the jit lets XLA cut the vocab-projection
        matmul to one position and shrink the output ``seq_len``-fold;
        :meth:`forward` keeps the reference's full-logits contract
        (reference ``engine.py:496``) for scoring-style callers."""
        if self._forward_last_fn is None:
            module = self.module

            def fwd(params, ids):
                return self._logits_of(module.apply(
                    {"params": self._dequantize(params)}, ids))[:, -1]

            self._forward_last_fn = self.telemetry.watch_jit(
                jax.jit(fwd), "inference.forward_last")
        t = self._timer("model_forward")   # same latency-collection
        t.start()                          # contract as forward()
        out = jax.block_until_ready(
            self._forward_last_fn(self.params, input_ids))
        t.stop()
        self._record_model_time("forward_last", t.elapsed(reset=True))
        return out

    def profile_model_time(self, use_cuda_events=None):
        """API parity with reference ``profile_model_time``
        (inference/engine.py:140): forward latencies are ALWAYS collected
        here (each jitted forward is block_until_ready-timed — the
        device-event machinery the reference opts into is the default on
        this path), so this only acknowledges the request.

        ``use_cuda_events`` is CUDA-era and retired: accepted for source
        compatibility, warned about, ignored."""
        if use_cuda_events is not None:
            import warnings

            warnings.warn(
                "profile_model_time(use_cuda_events=...) is CUDA-era and "
                "ignored on this backend: every jitted forward is fenced "
                "and wall-clock timed regardless", DeprecationWarning,
                stacklevel=2)
        self.model_profile_enabled = True

    def _record_model_time(self, name: str, seconds: float):
        """One forward/generate latency: buffered for :meth:`model_times`
        AND mirrored into the telemetry event stream (kind
        ``model_time``), so stream consumers see every entry even when a
        caller never drains the buffer."""
        self._model_times.append(seconds)
        self.telemetry.emit("model_time", name, step=self._request_count,
                            ms=round(1e3 * seconds, 4))

    def model_times(self):
        """Per-forward latencies (reference ``inference/engine.py:140,484``).
        Drains the buffer; the same entries ride the telemetry stream as
        ``model_time`` events when telemetry is enabled."""
        times = self._model_times
        self._model_times = []
        return times

    # ------------------------------------------------------------------
    def _build_generate(self, prompt_len: int, max_new_tokens: int,
                        do_sample: bool, top_k: int, top_p: float = 0.0,
                        padded: bool = False):
        dmodule = self._decode_module(padded)
        dequant = self._dequantize
        batch_spec = P(AXIS_DATA) if self.topo.axis_size(AXIS_DATA) > 1 else P()

        def generate_fn(qparams, input_ids, attention_mask, rng, temperature,
                        eos_id):
            params = dequant(qparams)
            input_ids = jax.lax.with_sharding_constraint(
                input_ids, NamedSharding(self.mesh, batch_spec))
            if padded:  # same batch layout as input_ids
                attention_mask = jax.lax.with_sharding_constraint(
                    attention_mask, NamedSharding(self.mesh, batch_spec))
            # prefill: one compiled program over the whole prompt (with a
            # left-padding mask, positions/keys follow each row's pads)
            kw = {"attention_mask": attention_mask} if padded else {}
            out, vars_ = dmodule.apply({"params": params}, input_ids,
                                       mutable=["cache"], **kw)
            logits = self._logits_of(out)
            cache = vars_["cache"]

            def sample(logits, rng):
                return sample_logits(logits, rng, temperature, do_sample,
                                     top_k, top_p)

            rng, sub = jax.random.split(rng)
            first = sample(logits[:, -1], sub)
            done = first == eos_id

            def body(carry, _):
                cache, token, rng, done = carry
                out, vars_ = dmodule.apply(
                    {"params": params, "cache": cache}, token[:, None],
                    mutable=["cache"])
                logits = self._logits_of(out)
                cache = vars_["cache"]
                rng, sub = jax.random.split(rng)
                nxt = sample(logits[:, -1], sub)
                nxt = jnp.where(done, eos_id, nxt)
                done = done | (nxt == eos_id)
                return (cache, nxt, rng, done), nxt

            (_, _, _, _), rest = jax.lax.scan(
                body, (cache, first, rng, done), None,
                length=max_new_tokens - 1)
            tokens = jnp.concatenate([first[:, None], rest.T], axis=1)
            return tokens

        return self.telemetry.watch_jit(
            jax.jit(generate_fn),
            # full build key in the label (one entry per compiled program);
            # the bracketed suffix is stripped for watchdog family grouping
            f"inference.generate[T={prompt_len},new={max_new_tokens},"
            f"sample={do_sample},k={top_k},p={top_p},padded={padded}]")

    def _build_generate_keyed(self, prompt_len: int, max_new_tokens: int,
                              padded: bool = False):
        """Reproducible keyed sampling for ``generate()``: every token
        is drawn from a threefry key folded from ``(seed, absolute
        position)`` inside the program — the SAME fold-in the serving
        engine's keyed decode performs — so a request decoded solo here
        emits bit-identical tokens to the same request decoded under
        continuous batching, migrated mid-stream, or replayed on
        failover. Temperature/top-k/top-p are traced (one compiled
        program covers every knob setting), so the cache keys only on
        shape."""
        from deepspeed_tpu.ops.sampling import keyed_sample

        dmodule = self._decode_module(padded)
        dequant = self._dequantize
        batch_spec = P(AXIS_DATA) if self.topo.axis_size(AXIS_DATA) > 1 else P()

        def generate_fn(qparams, input_ids, attention_mask, seed,
                        temperature, top_k, top_p, eos_id):
            params = dequant(qparams)
            input_ids = jax.lax.with_sharding_constraint(
                input_ids, NamedSharding(self.mesh, batch_spec))
            if padded:
                attention_mask = jax.lax.with_sharding_constraint(
                    attention_mask, NamedSharding(self.mesh, batch_spec))
            kw = {"attention_mask": attention_mask} if padded else {}
            out, vars_ = dmodule.apply({"params": params}, input_ids,
                                       mutable=["cache"], **kw)
            logits = self._logits_of(out)
            cache = vars_["cache"]
            B, T = input_ids.shape
            # the first generated token's absolute position is the REAL
            # prompt length — per row under left padding (mask sum), so
            # serving-bucket pads never shift the key stream
            pos0 = (jnp.sum(attention_mask, axis=1).astype(jnp.int32)
                    if padded else jnp.full((B,), T, jnp.int32))
            seeds = jnp.full((B,), seed, jnp.uint32)
            temps = jnp.full((B,), temperature, jnp.float32)
            ks = jnp.full((B,), top_k, jnp.int32)
            ps = jnp.full((B,), top_p, jnp.float32)
            flags = jnp.ones((B,), jnp.int32)

            def sample(step_logits, pos):
                return keyed_sample(step_logits, seeds, pos, flags, temps,
                                    ks, ps)

            first = sample(logits[:, -1], pos0)
            done = first == eos_id

            def body(carry, _):
                cache, token, pos, done = carry
                out, vars_ = dmodule.apply(
                    {"params": params, "cache": cache}, token[:, None],
                    mutable=["cache"])
                logits = self._logits_of(out)
                cache = vars_["cache"]
                pos = pos + 1
                nxt = sample(logits[:, -1], pos)
                nxt = jnp.where(done, eos_id, nxt)
                done = done | (nxt == eos_id)
                return (cache, nxt, pos, done), nxt

            (_, _, _, _), rest = jax.lax.scan(
                body, (cache, first, pos0, done), None,
                length=max_new_tokens - 1)
            tokens = jnp.concatenate([first[:, None], rest.T], axis=1)
            return tokens

        return self.telemetry.watch_jit(
            jax.jit(generate_fn),
            f"inference.generate[T={prompt_len},new={max_new_tokens},"
            f"keyed=True,padded={padded}]")

    def generate(self, input_ids, max_new_tokens: Optional[int] = None,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 0.0, eos_token_id: int = -1,
                 attention_mask=None, rng=None, seed: Optional[int] = None,
                 **kwargs):
        """Sharded autoregressive generation (reference ``engine.py:524``).

        Returns ``[batch, prompt_len + max_new_tokens]`` token ids (prompt
        included, HF-style). ``eos_token_id=-1`` disables early-stop padding.
        ``attention_mask`` ([B, T], 0 = LEFT padding) batches prompts of
        unequal length: per-row positions start at the first real token and
        padded cache slots are masked throughout decode.

        ``do_sample=True`` with ``seed`` set selects the KEYED sampler:
        token P is a pure function of (seed, P, logits), bit-identical to
        the serving engine's keyed decode of the same request — ``rng`` is
        ignored and the engine's rng stream is left untouched.
        """
        # resilience bracket: the hang-watchdog stall timer runs only
        # while a request is in flight (idle gaps between requests are
        # healthy); a raising request must clear its bracket or the idle
        # server would later be judged hung
        self.resilience.serving_request_begin()
        try:
            return self._generate_impl(
                input_ids, max_new_tokens=max_new_tokens,
                do_sample=do_sample, temperature=temperature, top_k=top_k,
                top_p=top_p, eos_token_id=eos_token_id,
                attention_mask=attention_mask, rng=rng, seed=seed, **kwargs)
        except BaseException:
            self.resilience.serving_request_abandon()
            raise

    def _generate_impl(self, input_ids, max_new_tokens: Optional[int] = None,
                       do_sample: bool = False, temperature: float = 1.0,
                       top_k: int = 0, top_p: float = 0.0,
                       eos_token_id: int = -1, attention_mask=None, rng=None,
                       seed: Optional[int] = None, **kwargs):
        input_ids = jnp.asarray(input_ids)
        if input_ids.ndim == 1:
            input_ids = input_ids[None]
        B, T = input_ids.shape
        # GPT-2 family names the window n_positions; Llama (and HF configs
        # generally) max_position_embeddings — missing BOTH would silently
        # overwrite the last cache slot once the window overflows
        limit = (getattr(self.model_config, "n_positions", None)
                 or getattr(self.model_config, "max_position_embeddings",
                            None))
        if max_new_tokens is None:
            cap = self._config.max_out_tokens
            if limit is not None:
                cap = min(cap, limit)
            max_new_tokens = cap - T
        if limit is not None and T + max_new_tokens > limit:
            raise ValueError(
                f"prompt ({T}) + max_new_tokens ({max_new_tokens}) exceeds "
                f"model window {limit}")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")

        if attention_mask is not None:
            # shared contract (decode_utils): left-padded, shape-matched,
            # all-real collapses to the unpadded fast path (Pallas decode
            # kernel + flash prefill)
            from deepspeed_tpu.models.decode_utils import (
                validate_left_padded_mask)

            attention_mask = validate_left_padded_mask(input_ids,
                                                       attention_mask)
        # serving-bucketed compile cache (satellite of the serving layer):
        # pad the prompt LEFT up to the bucket set so ad-hoc callers stop
        # compiling one program per distinct prompt length. Tokens are
        # unchanged (the padded-mask path proves parity in
        # test_padded_generate); the pad columns are stripped on return.
        trim = 0
        if self._serving_cfg is not None and self._serving_cfg.enabled \
                and self._serving_cfg.bucket_legacy_generate:
            input_ids, attention_mask, trim = self._bucket_prompt(
                input_ids, attention_mask, limit, max_new_tokens)
            T += trim
        padded = attention_mask is not None
        keyed = bool(do_sample) and seed is not None
        if keyed:
            # keyed sampler: knobs are TRACED (one program per shape, not
            # per knob setting) and the rng stream is untouched, so a
            # keyed call never perturbs a neighbouring greedy caller's
            # compile cache or reproducibility
            key = (T, int(max_new_tokens), "keyed", padded)
            if key not in self._generate_cache:
                self._generate_cache[key] = self._build_generate_keyed(
                    T, int(max_new_tokens), padded)
        else:
            key = (T, int(max_new_tokens), bool(do_sample), int(top_k),
                   float(top_p), padded)
            if key not in self._generate_cache:
                self._generate_cache[key] = self._build_generate(*key)
            if rng is None:
                self._rng, rng = jax.random.split(self._rng)
        t = self._timer("generate")
        t.start()
        if keyed:
            new = self._generate_cache[key](
                self.params, input_ids, attention_mask,
                jnp.asarray(int(seed) & 0xFFFFFFFF, jnp.uint32),
                jnp.asarray(temperature, jnp.float32),
                jnp.asarray(int(top_k), jnp.int32),
                jnp.asarray(float(top_p), jnp.float32),
                jnp.asarray(eos_token_id, jnp.int32))
        else:
            new = self._generate_cache[key](
                self.params, input_ids, attention_mask, rng,
                jnp.asarray(temperature, jnp.float32),
                jnp.asarray(eos_token_id, jnp.int32))
        new.block_until_ready()
        t.stop()
        self._record_model_time("generate", t.elapsed(reset=True))
        # request boundary: memory sample / trace window arming (the
        # block_until_ready above is the fence it piggybacks on)
        self._request_count += 1
        self.telemetry.on_step_boundary(self._request_count,
                                        samples=int(B))
        self.resilience.serving_heartbeat(self._request_count)
        out = np.concatenate([np.asarray(input_ids), np.asarray(new)], axis=1)
        return out[:, trim:] if trim else out

    def _bucket_prompt(self, input_ids, attention_mask, limit,
                       max_new_tokens):
        """Round the prompt length up to the serving bucket set by LEFT
        padding (plus a mask marking the pads), so ``_generate_cache``
        keys on a small fixed set of lengths. Skipped when the padded
        length would overflow the model window or the model lacks the
        padded decode path — those calls keep the exact-length program."""
        from deepspeed_tpu.serving.config import bucket_for, resolve_buckets

        B, T = input_ids.shape
        scfg = self._serving_cfg
        max_len = int(limit or self._config.max_out_tokens)
        buckets = resolve_buckets(scfg.prompt_buckets, max_len,
                                  floor=scfg.block_size)
        bT = bucket_for(T, buckets)
        if bT is None or bT == T:
            return input_ids, attention_mask, 0
        if limit is not None and bT + max_new_tokens > limit:
            return input_ids, attention_mask, 0  # pads would eat the window
        try:
            self._decode_module(padded=True)
        except ValueError:
            return input_ids, attention_mask, 0  # no padded decode support
        pad = bT - T
        if attention_mask is None:
            attention_mask = jnp.ones((B, T), jnp.int32)
        input_ids = jnp.concatenate(
            [jnp.zeros((B, pad), input_ids.dtype), input_ids], axis=1)
        attention_mask = jnp.concatenate(
            [jnp.zeros((B, pad), jnp.int32), attention_mask], axis=1)
        return input_ids, attention_mask, pad

    # ------------------------------------------------------------------
    def _save_mp_checkpoint(self, path, params_host):
        save_mp_checkpoint(path, params_host)

    # ------------------------------------------------------------------
    # reference checkpoint surface (engine.py:269,369)
    def load_checkpoint(self, load_dir, tag=None):
        params = load_module_params(load_dir, tag)
        params = self._convert_dtype(params)
        self.params, self.param_shardings = self._shard_params(params)
        if self._quantized:
            self.params, self._quant_meta = self._quantize_weights(self.params)
        self._generate_cache.clear()
        self._forward_fn = None
        self._forward_last_fn = None

    def destroy(self):
        """Release compiled programs and close telemetry (stopping any
        open trace window — XPlane data is only written on stop; the
        training engine's ``destroy`` does the same)."""
        self._generate_cache.clear()
        self._forward_fn = None
        self._forward_last_fn = None
        if getattr(self, "_tuned_install", None) is not None:
            from deepspeed_tpu.autotuning import runtime_tunables

            runtime_tunables.uninstall(self._tuned_install)
            self._tuned_install = None
        self.resilience.close()
        self.telemetry.close()

    def eval(self):
        return self

    def train(self, mode=False):
        return self
