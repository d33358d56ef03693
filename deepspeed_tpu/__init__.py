"""deepspeed_tpu — a TPU-native training/inference framework.

Re-designed from scratch for JAX/XLA/Pallas on TPU device meshes, with the
capability surface of the reference DeepSpeed (``deepspeed/__init__.py``):
``initialize()`` / ``init_inference()`` / ``add_config_arguments()``.
"""

import time as _time

_IMPORT_BEGAN = _time.monotonic()  # the start-up ledger's ``import`` stamp

from deepspeed_tpu.version import __version__, __version_info__

from deepspeed_tpu import zero
from deepspeed_tpu.accelerator import get_accelerator, set_accelerator
from deepspeed_tpu.runtime.activation_checkpointing import checkpointing
from deepspeed_tpu.runtime.config import DeepSpeedConfig, DeepSpeedConfigError


def init_distributed(dist_backend="xla", **kwargs):
    """Initialize the distributed runtime (reference
    ``deepspeed/__init__.py:578`` exposes this at top level; the
    implementation lives in :mod:`deepspeed_tpu.comm.comm`). Idempotent;
    single-process runs need no initialization."""
    from deepspeed_tpu.comm.comm import init_distributed as _init

    return _init(dist_backend=dist_backend, **kwargs)


def initialize(args=None,
               model=None,
               optimizer=None,
               model_parameters=None,
               training_data=None,
               lr_scheduler=None,
               mesh=None,
               dist_init_required=None,
               collate_fn=None,
               config=None,
               config_params=None):
    """Build a training engine around ``model``.

    Capability parity with reference ``deepspeed.initialize``
    (``deepspeed/__init__.py:52``). ``model`` is a flax module or any object
    exposing ``init(rng, batch)``/``apply(params, batch)``; ``mesh`` replaces
    the reference's ``mpu`` argument (a ``jax.sharding.Mesh`` or a
    ``deepspeed_tpu.parallel.MeshTopology``).

    Returns a tuple of ``engine, optimizer, training_dataloader, lr_scheduler``.
    """
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    from deepspeed_tpu.runtime.pipe.engine import PipelineEngine
    from deepspeed_tpu.runtime.pipe.module import PipelineModule
    from deepspeed_tpu.utils.logging import log_dist

    log_dist(f"DeepSpeed-TPU info: version={__version__}", ranks=[0])

    if config is None:
        config = config_params
    if config is None and args is not None and hasattr(args, "deepspeed_config"):
        config = args.deepspeed_config

    from deepspeed_tpu.runtime.zero.infinity import (ZeroInfinityEngine,
                                                     wants_param_offload)

    from deepspeed_tpu.utils.pytree import unwrap_variables_dict

    # flax variables-dict form (model.init output) — one shared unwrap so
    # EVERY engine class sees the bare param tree
    model_parameters = unwrap_variables_dict(model_parameters)

    if isinstance(model, PipelineModule):
        engine_cls = PipelineEngine
    elif wants_param_offload(config):
        # ZeRO-Infinity tier: parameters live on host/NVMe and stream to
        # the chip per layer (reference selects the stage-3 offload
        # machinery from the same config key)
        engine_cls = ZeroInfinityEngine
    else:
        engine_cls = DeepSpeedEngine
    engine = engine_cls(args=args,
                             model=model,
                             optimizer=optimizer,
                             model_parameters=model_parameters,
                             training_data=training_data,
                             lr_scheduler=lr_scheduler,
                             mesh=mesh,
                             dist_init_required=dist_init_required,
                             collate_fn=collate_fn,
                             config=config)
    return engine, engine.optimizer, engine.training_dataloader, engine.lr_scheduler


def default_inference_config():
    """Default inference config as a plain dict (reference
    ``deepspeed/__init__.py:226``)."""
    from deepspeed_tpu.inference.config import DeepSpeedInferenceConfig

    return DeepSpeedInferenceConfig().model_dump()


def init_inference(model, config=None, **kwargs):
    """Build an inference engine (reference ``deepspeed/__init__.py:233``).

    A ``zero`` section selecting stage-3 parameter offload (``{"stage": 3,
    "offload_param": {"device": "cpu"|"nvme", ...}}``) returns the
    ZeRO-Inference tier: parameters stay host/NVMe-resident and stream
    through the device per layer, serving models larger than device memory
    (reference ``docs/_posts/2022-09-10-zero-inference.md``)."""
    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.inference.zero_inference import (ZeroInferenceEngine,
                                                        wants_zero_inference)

    # probe ONLY the zero section ahead of engine construction (full
    # coercion — None/dict/instance + kwargs merge — lives in the engines;
    # duck-typed config objects must pass through untouched)
    zero = kwargs.get("zero")
    if zero is None:
        zero = (config.get("zero") if isinstance(config, dict)
                else getattr(config, "zero", None))
    if wants_zero_inference(zero):
        return ZeroInferenceEngine(model, config=config, **kwargs)
    return InferenceEngine(model, config=config, **kwargs)


def init_serving(model, config=None, replicas=None, factory=None,
                 clock=None, **kwargs):
    """Build the continuous-batching serving runtime (paged KV cache +
    request scheduler) over an inference engine. ``model`` may be a flax
    model (a fresh :class:`InferenceEngine` is built from ``config`` /
    ``kwargs``, which must carry a ``serving`` block) or an existing
    :class:`InferenceEngine` whose config already has one.

    With a ``serving.router`` block the result is the resilient
    multi-replica front door instead
    (:class:`~deepspeed_tpu.serving.router.ReplicaRouter`):
    ``serving.router.replicas`` independent engines are built from
    ``model`` — or ``replicas`` is a pre-built list (InferenceEngines
    are wrapped, anything already exposing the ServingEngine surface is
    taken as-is) — behind one submit()/step()/drain() surface with
    health-aware routing, deterministic-replay failover, and the
    SLO-guarded degradation ladder. Without the block nothing changes:
    the single engine is returned and its compiled programs are
    byte-identical to previous releases.

    With a ``serving.fleet`` block on top the router is wrapped in the
    elastic :class:`~deepspeed_tpu.serving.router.FleetManager` (SLO
    error-budget autoscaling through the drain/reactivate seams):
    scale-up builds fresh replicas through ``factory`` — a
    :class:`~deepspeed_tpu.serving.router.ReplicaFactory` or a zero-arg
    builder callable; when building engines from ``model``, the default
    factory clones the same build, so the warm AOT/tuning path is
    whatever the caller's config restores. ``clock`` injects the
    router/fleet timebase (default ``time.monotonic``) — pass the
    trace-replay harness's ``ReplayClock`` to drive the whole front
    door faster than real time.

    With a ``serving.gateway`` block the whole stack goes behind the
    HTTP/SSE front door: the result is a live
    :class:`~deepspeed_tpu.serving.gateway.ServingGateway` (already
    ``start()``-ed — read ``.port``) over whichever backend the other
    blocks selected, with per-tenant API keys, token-bucket quotas and
    SLO classes from the block. Without it nothing changes — the
    gateway does not exist and no socket is opened."""
    from deepspeed_tpu.serving import ServingEngine

    def _on(block):
        # the standard config off switch: block present, layer disabled
        # — identical to absent
        if block is None:
            return None
        enabled = (block.get("enabled", True) if isinstance(block, dict)
                   else getattr(block, "enabled", True))
        return block if enabled else None

    def _behind_gateway(backend, gateway_block):
        gateway_block = _on(gateway_block)
        if gateway_block is None:
            return backend
        from deepspeed_tpu.serving.gateway import ServingGateway
        gw_clock = clock if clock is not None \
            else getattr(backend, "clock", None)
        gw_kwargs = {} if gw_clock is None else {"clock": gw_clock}
        return ServingGateway(backend, config=gateway_block,
                              **gw_kwargs).start()

    # probe ONLY router presence ahead of construction (full coercion
    # lives in ServingConfig); `replicas` alone also selects the router
    serving = kwargs.get("serving")
    if serving is None:
        serving = (config.get("serving") if isinstance(config, dict)
                   else getattr(config, "serving", None))
    if serving is None:
        # a prebuilt InferenceEngine carries its serving block — a
        # router configured there must not be silently dropped
        serving = getattr(model, "_serving_cfg", None)
    router = (serving.get("router") if isinstance(serving, dict)
              else getattr(serving, "router", None))
    if router is not None and not (router.get("enabled", True)
                                   if isinstance(router, dict)
                                   else getattr(router, "enabled", True)):
        router = None  # the standard config off switch: block present,
        #                layer disabled — identical to absent
    fleet = (serving.get("fleet") if isinstance(serving, dict)
             else getattr(serving, "fleet", None))
    if fleet is not None and not (fleet.get("enabled", True)
                                  if isinstance(fleet, dict)
                                  else getattr(fleet, "enabled", True)):
        fleet = None  # standard off switch, same as the router block
    gateway = (serving.get("gateway") if isinstance(serving, dict)
               else getattr(serving, "gateway", None))
    clock_kwargs = {} if clock is None else {"clock": clock}
    if router is None and replicas is None:
        engine = ServingEngine(model, config=config, **clock_kwargs,
                               **kwargs)
        if gateway is None:
            gateway = getattr(engine.config, "gateway", None)
        return _behind_gateway(engine, gateway)

    from deepspeed_tpu.inference.engine import InferenceEngine
    from deepspeed_tpu.serving.router import (CallableReplicaFactory,
                                              FleetManager, ReplicaRouter)

    built_from_model = False
    if replicas is None or isinstance(replicas, int):
        if isinstance(model, InferenceEngine):
            raise ValueError(
                "one InferenceEngine is one replica — pass the prebuilt "
                "engines as a list via `replicas` instead of a count")
        built_from_model = True
        first = ServingEngine(model, config=config, **clock_kwargs,
                              **kwargs)
        count = replicas if isinstance(replicas, int) else (
            first.config.router.replicas if first.config.router else 2)
        engines = [first] + [ServingEngine(model, config=config,
                                           **clock_kwargs, **kwargs)
                             for _ in range(count - 1)]
    else:
        engines = [ServingEngine(r, **clock_kwargs)
                   if isinstance(r, InferenceEngine) else r
                   for r in replicas]

    def _carried(field):
        # prebuilt replicas, no explicit block: fall back to a config an
        # engine carries (explicit caller blocks always win)
        return next(
            (c for c in (getattr(getattr(e, "config", None), field, None)
                         for e in engines) if c is not None), None)

    if router is None:
        router = _carried("router")
    if fleet is None:
        # same fallback as the router block: an engine-carried fleet
        # config silently dropped would read as "autoscaling is on"
        # when it is not
        fleet = _carried("fleet")
        if fleet is not None and not getattr(fleet, "enabled", True):
            fleet = None
    # live KV migration block (same carry rules): absent/disabled means
    # the router's failover/drain behavior is byte-for-byte pre-PR-18
    migration = (serving.get("migration") if isinstance(serving, dict)
                 else getattr(serving, "migration", None))
    if migration is None:
        migration = _carried("migration")
    if gateway is None:
        gateway = _carried("gateway")
    front = ReplicaRouter(engines, config=router, migration=migration,
                          **clock_kwargs)
    if fleet is None:
        if factory is not None:
            raise ValueError(
                "init_serving got a replica `factory` but no "
                "serving.fleet block — the factory is the fleet "
                "manager's scale-up seam; add \"fleet\": {...} to use it")
        return _behind_gateway(front, gateway)
    if factory is None and built_from_model:
        # same build as the initial replicas: whatever AOT/tuning warm
        # path the caller's config restores, a scaled-up replica gets too
        factory = CallableReplicaFactory(
            lambda: ServingEngine(model, config=config, **clock_kwargs,
                                  **kwargs))
    return _behind_gateway(
        FleetManager(front, factory=factory, config=fleet), gateway)


def add_config_arguments(parser):
    """Add ``--deepspeed``/``--deepspeed_config`` args (reference ``:159-207``)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag to indicate usage)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="Path to DeepSpeed json configuration")
    group.add_argument("--deepscale", default=False, action="store_true",
                       help="Deprecated enable flag")
    group.add_argument("--deepscale_config", default=None, type=str,
                       help="Deprecated config path")
    group.add_argument("--deepspeed_mpi", default=False, action="store_true",
                       help="Run via MPI discovery")
    return parser


def __getattr__(name):
    """Lazy top-level classes the reference exposes from ``deepspeed``
    directly (``DeepSpeedEngine``, ``InferenceEngine``, ...) — resolved on
    first touch so importing the package stays light."""
    lazy = {
        "DeepSpeedEngine": ("deepspeed_tpu.runtime.engine", "DeepSpeedEngine"),
        "PipelineEngine": ("deepspeed_tpu.runtime.pipe.engine",
                           "PipelineEngine"),
        "InferenceEngine": ("deepspeed_tpu.inference.engine",
                            "InferenceEngine"),
        "PipelineModule": ("deepspeed_tpu.runtime.pipe.module",
                           "PipelineModule"),
        "OnDevice": ("deepspeed_tpu.utils.init_on_device", "OnDevice"),
    }
    if name in lazy:
        import importlib

        mod, sym = lazy[name]
        return getattr(importlib.import_module(mod), sym)
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")


# the last line: the package's import, into the process's start-up ledger
from deepspeed_tpu.telemetry import process_ledger as _process_ledger

_process_ledger.LEDGER.stamp_import(_IMPORT_BEGAN, _time.monotonic())
