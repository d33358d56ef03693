"""Master DeepSpeed-style JSON config.

Capability parity with the reference ``deepspeed/runtime/config.py``
(``DeepSpeedConfig``, batch-size triangle at ``:918-989``, ~70 ``get_*``
helpers), re-based on a pydantic tree plus a TPU-native ``mesh`` section that
declares named mesh axis sizes (data/fsdp/tp/pipe/expert/seq; ``model`` is
the deprecated alias of ``tp``) instead of the reference's implicit
world-size + mpu plumbing.
"""

import json
import os
from typing import Any, Dict, Optional

from pydantic import Field, model_validator

from deepspeed_tpu.runtime import constants as C
from deepspeed_tpu.runtime.config_utils import (
    DeepSpeedConfigModel,
    dict_raise_error_on_duplicate_keys,
)
from deepspeed_tpu.runtime.precision_config import AMPConfig, BF16Config, FP16Config
from deepspeed_tpu.runtime.zero.config import DeepSpeedZeroConfig
from deepspeed_tpu.utils.logging import logger


class DeepSpeedConfigError(Exception):
    pass


class MeshConfig(DeepSpeedConfigModel):
    """TPU-native: named mesh axis sizes. ``data`` may be -1 (fill remaining
    devices). The reference derives parallel dims from world size + an external
    mpu (``deepspeed/utils/groups.py``); here the mesh is declared.

    The 3-axis training/serving layout is ``{data: D, fsdp: F, tp: T}``
    (SpecLayout, ``runtime/zero/partition.py``): ``fsdp`` shards
    weights/optimizer state beyond the data axis (never the batch), ``tp``
    shards weight dims per parameter family. ``model`` is the accepted
    pre-3-axis alias for ``tp``."""

    data: int = -1
    fsdp: int = 1
    tp: int = 1
    # deprecated alias for tp (pre-3-axis-mesh configs); folded into tp
    # by the validator below
    model: int = Field(1, json_schema_extra={"deprecated": "alias of tp"})
    pipe: int = 1
    expert: int = 1
    seq: int = 1
    axis_order: tuple = ("pipe", "data", "fsdp", "expert", "seq", "tp")
    # multi-slice/multi-pod: per-axis factor that crosses the DCN (slice)
    # boundary, e.g. {"data": 4} trains 4 pods data-parallel with all other
    # axes riding ICI inside each pod (reference: multinode NCCL topology;
    # here jax mesh_utils.create_hybrid_device_mesh places the axes)
    dcn: dict = Field(default_factory=dict)

    @model_validator(mode="after")
    def _fold_model_alias(self):
        if self.model != 1:
            if self.tp not in (1, self.model):
                raise ValueError(
                    f"mesh names both tp={self.tp} and its deprecated "
                    f"alias model={self.model} with different sizes — "
                    "keep only tp")
            # object.__setattr__: plain assignment would re-enter this
            # validator via validate_assignment
            object.__setattr__(self, "tp", self.model)
        return self


class ActivationCheckpointingConfig(DeepSpeedConfigModel):
    """Reference ``runtime/activation_checkpointing/config.py``. On TPU this
    selects a ``jax.checkpoint`` (remat) policy; partition_activations maps to
    sharding the saved residuals over the model axis."""

    # TPU-native extensions: presence of the config section enables remat
    # (set ``enabled: false`` to override); ``policy`` picks the
    # jax.checkpoint granularity — "full" recomputes whole blocks, "dots"
    # saves matmul outputs and recomputes only elementwise chains
    enabled: bool = True
    policy: str = "full"
    # reference checkpointing.py:372 — saved inter-layer residuals get a
    # sharding constraint spreading seq over the model axis (stored
    # sharded, all-gathered at recompute)
    partition_activations: bool = False
    contiguous_memory_optimization: bool = False  # INERT: engine warns
    # reference checkpointing.py:485 — saved inter-layer residuals are
    # host-offloaded via a save_and_offload_only_these_names remat policy
    cpu_checkpointing: bool = False
    number_checkpoints: Optional[int] = None  # INERT: engine warns
    synchronize_checkpoint_boundary: bool = False  # INERT: engine warns
    profile: bool = False  # INERT: engine warns


class CommsLoggerConfig(DeepSpeedConfigModel):
    """Reference ``deepspeed/comm/config.py``."""

    enabled: bool = False
    verbose: bool = False
    prof_all: bool = True
    debug: bool = False
    prof_ops: list = Field(default_factory=list)


class FlopsProfilerConfig(DeepSpeedConfigModel):
    """Reference ``deepspeed/profiling/config.py``."""

    enabled: bool = False
    profile_step: int = 1
    module_depth: int = -1
    top_modules: int = 1
    detailed: bool = True
    output_file: Optional[str] = None


class TensorBoardConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class WandbConfig(DeepSpeedConfigModel):
    enabled: bool = False
    group: Optional[str] = None
    team: Optional[str] = None
    project: str = "deepspeed"


class CSVConfig(DeepSpeedConfigModel):
    enabled: bool = False
    output_path: str = ""
    job_name: str = "DeepSpeedJobName"


class MonitorConfig(DeepSpeedConfigModel):
    """Reference ``deepspeed/monitor/config.py`` (flattened sections)."""

    tensorboard: TensorBoardConfig = Field(default_factory=TensorBoardConfig)
    wandb: WandbConfig = Field(default_factory=WandbConfig)
    csv_monitor: CSVConfig = Field(default_factory=CSVConfig)

    @property
    def enabled(self):
        return self.tensorboard.enabled or self.wandb.enabled or self.csv_monitor.enabled


class CheckpointConfig(DeepSpeedConfigModel):
    tag_validation: str = "Warn"

    @model_validator(mode="after")
    def _check_tag_validation(self):
        from deepspeed_tpu.runtime.constants import CHECKPOINT_TAG_VALIDATION_MODES

        normalized = self.tag_validation.capitalize()
        if normalized not in CHECKPOINT_TAG_VALIDATION_MODES:
            raise ValueError(
                f"checkpoint.tag_validation must be one of {CHECKPOINT_TAG_VALIDATION_MODES}, "
                f"got {self.tag_validation!r}")
        if normalized != self.tag_validation:
            object.__setattr__(self, "tag_validation", normalized)
        return self

    load_universal: bool = False
    use_node_local_storage: bool = False
    parallel_write: dict = Field(default_factory=dict)
    async_save: bool = False  # TPU-native: orbax-style async checkpointing
    # sharded: each host writes only its addressable shards (orbax/tensorstore
    # parallel write) — no consolidation, and restore can re-shard onto a
    # different mesh (the universal-checkpoint capability, reference
    # checkpoint/universal_checkpoint.py:13). False = consolidated npz.
    sharded: bool = False


class NebulaConfig(DeepSpeedConfigModel):
    """``nebula`` section (reference ``nebula/config.py``): service-style
    tiered checkpointing — fast-tier commits + periodic durable mirror
    with version retention. Served by ``TieredCheckpointEngine``."""

    enabled: bool = False
    persistent_storage_path: Optional[str] = None
    persistent_time_interval: float = 100.0
    num_of_version_in_retention: int = 2
    enable_nebula_load: bool = True
    load_path: Optional[str] = None


class DataTypesConfig(DeepSpeedConfigModel):
    grad_accum_dtype: Optional[str] = None


class CommQuantizationConfig(DeepSpeedConfigModel):
    """``comm_quantization`` section: wire format of the gradient-reduction
    collectives (TPU-native; the reference's nearest knob is
    ``communication_data_type`` plus the 1-bit optimizer family).

    - ``dtype``: ``"none"`` keeps the full-width carrier (bucketing still
      applies), ``"int8"`` runs the EQuARX-style two-leg quantized
      allreduce (``runtime/comm/quantized.py``), ``"1bit"`` selects the
      packed sign wire — valid only with a 1-bit optimizer, whose state
      carries the error feedback.
    - ``group_size``: elements per int8 scale chunk.
    - ``bucket_bytes``: byte budget per reduction bucket; each bucket is an
      independent collective that overlaps remaining backward compute
      (``runtime/zero/reduce.py``).
    - ``onebit_carrier``: wire carrier for the 1-bit optimizer family —
      ``"packed"`` (uint8 bitfield all-gather, the 32x DCN cut) or
      ``"dense"`` (f32 psum of sign x scale, the semantics baseline).
    """

    enabled: bool = False
    dtype: str = "int8"
    group_size: int = 1024
    bucket_bytes: int = 16 * 1024 * 1024
    onebit_carrier: str = "packed"

    @model_validator(mode="after")
    def _check(self):
        if self.dtype not in ("none", "int8", "1bit"):
            raise ValueError(
                f"comm_quantization.dtype must be one of none/int8/1bit, "
                f"got {self.dtype!r}")
        if self.onebit_carrier not in ("packed", "dense"):
            raise ValueError(
                f"comm_quantization.onebit_carrier must be packed or dense, "
                f"got {self.onebit_carrier!r}")
        if self.group_size <= 0 or self.bucket_bytes <= 0:
            raise ValueError(
                "comm_quantization.group_size and bucket_bytes must be "
                "positive")
        return self


class PipelineConfig(DeepSpeedConfigModel):
    """``pipeline`` section: which instruction schedule the pipeline
    engine compiles (``runtime/pipe/schedule.py``).

    - ``schedule``: ``"1f1b"`` (default — the existing schedule, byte-
      identical HLO when this section is absent), ``"interleaved"``
      (``virtual_stages`` round-robin layer chunks per physical stage,
      bubble shrinks toward ``(P-1)/(Mv+P-1)`` for ``v``x activation
      buffers), or ``"zero_bubble"`` (ZB-H1 split backward — the
      instruction stream models ``BackwardInput``/``BackwardWeight``;
      the compiled program is unchanged because XLA's scan transpose
      already owns the backward ordering, so losses stay bit-identical
      to 1F1B).
    - ``virtual_stages``: chunks per physical stage; only meaningful
      with ``schedule: interleaved``; layers must divide stages *
      virtual_stages.
    """

    schedule: str = "1f1b"
    virtual_stages: int = 1

    @model_validator(mode="after")
    def _check(self):
        if self.schedule not in ("1f1b", "interleaved", "zero_bubble"):
            raise ValueError(
                "pipeline.schedule must be one of 1f1b/interleaved/"
                f"zero_bubble, got {self.schedule!r}")
        if self.virtual_stages < 1:
            raise ValueError("pipeline.virtual_stages must be >= 1")
        if self.virtual_stages > 1 and self.schedule != "interleaved":
            raise ValueError(
                "pipeline.virtual_stages > 1 requires "
                "pipeline.schedule == 'interleaved'")
        return self


class TelemetryTraceConfig(DeepSpeedConfigModel):
    """``telemetry.trace``: capture a ``jax.profiler`` XPlane trace for
    exactly ``num_steps`` optimizer steps starting once ``start_step``
    steps have completed (``num_steps == 0`` disables the window)."""

    start_step: int = 0
    num_steps: int = 0
    dir: str = "./telemetry/trace"

    @model_validator(mode="after")
    def _check(self):
        if self.start_step < 0 or self.num_steps < 0:
            raise ValueError("telemetry.trace.start_step/num_steps must be "
                             ">= 0")
        return self


class TelemetryTracingConfig(DeepSpeedConfigModel):
    """``telemetry.tracing``: span-based causal tracing
    (``telemetry/tracing.py``) — serving request traces and training
    step-phase traces as ``span`` events on the stream. Off by default;
    enabling it changes host-side bookkeeping only (the compiled
    step/decode HLO stays byte-identical, pinned in
    ``tests/unit/test_tracing.py``)."""

    enabled: bool = False


class TelemetryFlightRecorderConfig(DeepSpeedConfigModel):
    """``telemetry.flight_recorder``: a bounded in-memory ring of recent
    telemetry events (spans included) + metric-registry snapshots,
    continuously armed while telemetry is on and dumped atomically to
    ``<dump_dir>/flightrec-<ts>/`` on fault events, breaker trips,
    SIGTERM, or an explicit call — the "what was happening in the 30 s
    before the watchdog killed us" artifact. Off by default; enabling
    it changes host-side bookkeeping only (the compiled step/decode HLO
    stays byte-identical, pinned in tests/unit/test_metrics_plane.py).
    """

    enabled: bool = False
    events: int = 512          # event-ring capacity (spans ride it too)
    snapshots: int = 64        # metric-snapshot ring (0 disables)
    dump_dir: Optional[str] = None   # default: <telemetry.dir>
    max_dumps: int = 4         # per-process dump budget (fault storms
    #                            must not fill the disk)
    on_sigterm: bool = True    # chain a SIGTERM handler (preemption dump)

    @model_validator(mode="after")
    def _check(self):
        if self.events <= 0 or self.snapshots < 0 or self.max_dumps < 1:
            raise ValueError(
                "telemetry.flight_recorder needs events > 0, "
                "snapshots >= 0 and max_dumps >= 1")
        return self


class TelemetryConfig(DeepSpeedConfigModel):
    """``telemetry`` section (TPU-native): the unified observability event
    stream (``deepspeed_tpu/telemetry/``). Four collectors:

    - ``compile_watchdog``: per-jitted-function compile wall time and
      retrace count, with loud warnings on recompile storms after
      ``warmup_steps`` (``recompile_warn_after`` recompiles trip it).
    - ``hlo_cost``: once per compile, FLOPs / per-collective wire bytes /
      executable memory analysis from the compiled step program.
    - ``memory``: device memory stats sampled every ``sample_every`` step
      boundaries, passively (no added host syncs).
    - ``trace``: config-driven ``jax.profiler`` trace window.

    Events land in a rank-0-gated JSON-lines sink at
    ``<dir>/telemetry.jsonl`` (``jsonl: false`` keeps collectors live for
    the monitor bridge only) — render it with
    ``python tools/telemetry_report.py <path>``.
    """

    enabled: bool = False
    dir: str = "./telemetry"
    jsonl: bool = True
    # size-bounded sink: rotate the live telemetry.jsonl once it reaches
    # rotate_bytes (0 = never), keeping the last rotate_keep rotated
    # segments (<path>.1 newest .. <path>.K oldest)
    rotate_bytes: int = 0
    rotate_keep: int = 4
    compile_watchdog: bool = True
    hlo_cost: bool = True
    memory: bool = True
    sample_every: int = 1
    warmup_steps: int = 1
    recompile_warn_after: int = 1
    # live metrics plane (telemetry/registry.py + prom.py): a labeled
    # Counter/Gauge/Histogram registry with OpenMetrics/Prometheus text
    # exposition. metrics_port arms the registry AND serves it from a
    # stdlib http.server endpoint per process (0 = ephemeral port; None
    # = no server). metrics_file arms the registry and atomically dumps
    # the exposition text there at step boundaries (the scrape-less
    # path). Both absent (default): the registry is the inert
    # NULL_REGISTRY and nothing changes anywhere.
    metrics_port: Optional[int] = None
    metrics_host: str = "127.0.0.1"
    metrics_file: Optional[str] = None
    trace: TelemetryTraceConfig = Field(default_factory=TelemetryTraceConfig)
    tracing: TelemetryTracingConfig = Field(
        default_factory=TelemetryTracingConfig)
    flight_recorder: TelemetryFlightRecorderConfig = Field(
        default_factory=TelemetryFlightRecorderConfig)

    @model_validator(mode="after")
    def _check(self):
        if self.sample_every <= 0:
            raise ValueError("telemetry.sample_every must be positive")
        if self.warmup_steps < 0 or self.recompile_warn_after < 1:
            raise ValueError("telemetry.warmup_steps must be >= 0 and "
                             "recompile_warn_after >= 1")
        if self.rotate_bytes < 0 or self.rotate_keep < 1:
            raise ValueError("telemetry.rotate_bytes must be >= 0 and "
                             "rotate_keep >= 1")
        if self.metrics_port is not None and not (
                0 <= self.metrics_port <= 65535):
            raise ValueError("telemetry.metrics_port must be a valid "
                             "port (0 binds an ephemeral one) or absent")
        return self


class TuningConfig(DeepSpeedConfigModel):
    """``tuning`` section (TPU-native): consume a measured tuned-config
    artifact (``autotuning/artifact.py``) at engine build.

    - ``artifact``: path to ``tuned.json`` (default:
      ``<results_dir>/tuned.json``) — written by the live autotuner
      (``python -m deepspeed_tpu.autotuning --live`` or
      :class:`~deepspeed_tpu.autotuning.measure.LiveTuner`).
    - Precedence is explicit-user-key > artifact > default: a key this
      config file sets is never overridden by the artifact.
    - The artifact is fingerprint-pinned: consuming it on a different
      topology raises a structured
      :class:`~deepspeed_tpu.autotuning.artifact.TunedArtifactError`
      listing saved-vs-current fields.

    With the block absent nothing changes anywhere: no artifact is read,
    no kernel default is overridden, and the compiled step HLO is
    byte-identical (zero-overhead contract, pinned in
    ``tests/unit/test_live_tuning.py``).
    """

    enabled: bool = False
    artifact: Optional[str] = None
    results_dir: str = "autotuning_results"


class AOTConfig(DeepSpeedConfigModel):
    """``aot`` section (TPU-native): ship the engine's steady-state
    compiled executables with every checkpoint (``deepspeed_tpu/aot``)
    and pre-populate dispatch on resume, so a same-topology restart
    reaches its first step without recompiling the world.

    Requires ``telemetry.enabled`` (with the compile watchdog or HLO
    cost collector on): the telemetry ``WatchedFunction`` layer is what
    holds the compiled executables. Enabling ``aot`` without it is a
    config error, not a silent no-op.

    - ``fail_on_mismatch``: a shipped bundle whose identity (jaxlib
      version, topology fingerprint, tuned-config hash) mismatches the
      live runtime raises instead of warning + compiling normally.
      (Not named ``strict``: the base config model's constructor
      consumes that kwarg for auto-value handling.)
    """

    enabled: bool = False
    fail_on_mismatch: bool = False


class ResilienceCheckpointConfig(DeepSpeedConfigModel):
    """``resilience.checkpoint``: integrity manifests + fallback chain +
    IO retry + retention (``runtime/resilience/integrity.py``).

    - ``integrity``: write a per-file sha256 manifest as the ``commit()``
      step and record the tag verified-good.
    - ``verify_on_load``: re-check the manifest before any bytes
      deserialize; a mismatch raises ``CheckpointCorruptionError`` and
      (on a ``latest`` resume) falls back down the verified-good chain.
    - ``fallback``: enable the resume fallback chain
      (``latest`` → previous verified-good tags, newest first).
    - ``retries`` / ``retry_backoff_secs``: transient save/load IO errors
      retry with exponential backoff (``backoff * 2**attempt``).
    - ``keep_last_n``: retention over *verified* tags; ``0`` keeps all.
      The newest verified-good tag and the elastic agent's ``preempt``
      tag are never deleted.
    - ``rollback_dir``: pins where ``sentinel.policy: rollback`` restores
      from (default: the last ``save_checkpoint`` directory).
    """

    integrity: bool = True
    verify_on_load: bool = True
    fallback: bool = True
    retries: int = 3
    retry_backoff_secs: float = 0.2
    keep_last_n: int = 0
    rollback_dir: Optional[str] = None

    @model_validator(mode="after")
    def _check(self):
        if self.retries < 0 or self.retry_backoff_secs < 0:
            raise ValueError("resilience.checkpoint.retries and "
                             "retry_backoff_secs must be >= 0")
        if self.keep_last_n < 0:
            raise ValueError("resilience.checkpoint.keep_last_n must be "
                             ">= 0 (0 keeps everything)")
        return self


class ResilienceSentinelConfig(DeepSpeedConfigModel):
    """``resilience.sentinel``: NaN/Inf + loss-spike detection at every
    optimizer boundary (``runtime/resilience/sentinel.py``) — the bf16
    protection the fp16 overflow path never covered.

    - ``policy``: ``warn`` (log + fault event) | ``skip`` (compile the
      fp16-style grads NaN/Inf check into the step: a bad step is
      skipped exactly like an fp16 overflow) | ``abort`` (raise out of
      ``engine.step()``) | ``rollback`` (restore the last verified-good
      checkpoint in place).
    - ``loss_spike_factor``: trip when loss > factor x trailing-window
      mean (``0`` disables spike detection; nonfinite always trips).
    - ``loss_window`` / ``min_history``: trailing window size and the
      minimum samples before spike detection arms.
    - ``sync_lag``: boundaries to hold each loss before the host reads it
      (``0`` checks immediately at the cost of run-ahead).
    - ``max_rollbacks``: rollbacks tolerated before escalating to abort
      (``0`` = unlimited).
    """

    enabled: bool = True
    policy: str = "warn"
    loss_spike_factor: float = 0.0
    loss_window: int = 32
    min_history: int = 4
    sync_lag: int = 1
    max_rollbacks: int = 3

    @model_validator(mode="after")
    def _check(self):
        if self.policy not in ("warn", "skip", "abort", "rollback"):
            raise ValueError(
                "resilience.sentinel.policy must be one of warn/skip/"
                f"abort/rollback, got {self.policy!r}")
        if self.loss_window <= 0 or self.min_history < 1:
            raise ValueError("resilience.sentinel.loss_window must be > 0 "
                             "and min_history >= 1")
        if self.sync_lag < 0 or self.loss_spike_factor < 0 \
                or self.max_rollbacks < 0:
            raise ValueError("resilience.sentinel.sync_lag, "
                             "loss_spike_factor and max_rollbacks must be "
                             ">= 0")
        return self


class ResilienceWatchdogConfig(DeepSpeedConfigModel):
    """``resilience.watchdog``: background stall detector
    (``runtime/resilience/watchdog.py``). Arms at the first completed
    optimizer step (initial compiles can never trip it); on
    ``timeout_secs`` without step progress it dumps every Python thread's
    stack + the telemetry event tail to ``dump_dir`` and (``abort``)
    SIGTERMs then hard-exits with ``exit_code`` so the supervisor
    restarts the job."""

    enabled: bool = True
    timeout_secs: float = 600.0
    poll_secs: float = 0.0  # 0 = auto (timeout/4, capped at 10s)
    dump_dir: str = "./resilience"
    abort: bool = True
    exit_code: int = 43

    @model_validator(mode="after")
    def _check(self):
        if self.timeout_secs <= 0 or self.poll_secs < 0:
            raise ValueError("resilience.watchdog.timeout_secs must be > 0 "
                             "and poll_secs >= 0")
        return self


class ResilienceConfig(DeepSpeedConfigModel):
    """``resilience`` section (TPU-native): the fault-tolerance layer
    (``deepspeed_tpu/runtime/resilience/``). Off by default; with the
    block absent or disabled the compiled train step is byte-identical
    to a resilience-free build (same zero-overhead contract as
    ``telemetry``)."""

    enabled: bool = False
    checkpoint: ResilienceCheckpointConfig = Field(
        default_factory=ResilienceCheckpointConfig)
    sentinel: ResilienceSentinelConfig = Field(
        default_factory=ResilienceSentinelConfig)
    watchdog: ResilienceWatchdogConfig = Field(
        default_factory=ResilienceWatchdogConfig)


def _resolve_batch_triangle(train_batch, micro_batch, gas, dp_world_size):
    """Resolve/validate train_batch = micro_batch * gas * dp_world.

    Mirrors reference ``DeepSpeedConfig._configure_train_batch_size``
    (``runtime/config.py:918-989``): any two given determine the third; one
    given fills the others with sensible defaults; none given is an error.
    """
    tb, mb, g = train_batch, micro_batch, gas
    if tb is not None and mb is not None and g is not None:
        if tb != mb * g * dp_world_size:
            raise DeepSpeedConfigError(
                f"Check batch related parameters. train_batch_size is not equal to "
                f"micro_batch_per_gpu * gradient_acc_step * world_size "
                f"{tb} != {mb} * {g} * {dp_world_size}"
            )
    elif tb is not None and mb is not None:
        g, rem = divmod(tb, mb * dp_world_size)
        if rem != 0:
            raise DeepSpeedConfigError(
                f"train_batch_size {tb} not divisible by micro_batch {mb} * world size {dp_world_size}"
            )
    elif tb is not None and g is not None:
        mb, rem = divmod(tb, g * dp_world_size)
        if rem != 0:
            raise DeepSpeedConfigError(
                f"train_batch_size {tb} not divisible by gas {g} * world size {dp_world_size}"
            )
    elif mb is not None and g is not None:
        tb = mb * g * dp_world_size
    elif tb is not None:
        g = 1
        mb, rem = divmod(tb, dp_world_size)
        if rem != 0:
            raise DeepSpeedConfigError(f"train_batch_size {tb} not divisible by world size {dp_world_size}")
    elif mb is not None:
        g = 1
        tb = mb * dp_world_size
    else:
        raise DeepSpeedConfigError(
            "Either train_batch_size or train_micro_batch_size_per_gpu needs to be provided"
        )
    for name, v in (("train_batch_size", tb), ("train_micro_batch_size_per_gpu", mb),
                    ("gradient_accumulation_steps", g)):
        if v <= 0:
            raise DeepSpeedConfigError(f"{name} must be positive, got {v}")
    return tb, mb, g


class DeepSpeedConfig:
    """Parsed master config.

    ``config`` may be a dict, a path to a JSON file, or None. ``world_size``
    here means the *data-parallel* world size used in batch arithmetic
    (reference passes ``mpu.get_data_parallel_world_size()``).
    """

    def __init__(self, config: Any, mpu=None, world_size: Optional[int] = None):
        if isinstance(config, str):
            if not os.path.exists(config):
                raise DeepSpeedConfigError(f"DeepSpeed config path does not exist: {config}")
            with open(config) as f:
                self._param_dict = json.load(f, object_pairs_hook=dict_raise_error_on_duplicate_keys)
        elif isinstance(config, dict):
            self._param_dict = dict(config)
        elif config is None:
            self._param_dict = {}
        else:
            raise DeepSpeedConfigError(
                f"Expected a string path to a JSON file or a dict, got: {type(config)}")

        d = self._param_dict
        # --- sub-models ---
        self.fp16 = FP16Config(**d.get(C.FP16, {}))
        self.bf16 = BF16Config(**d.get(C.BF16, d.get("bfloat16", {})))
        self.amp = AMPConfig(**d.get(C.AMP, {}))
        self.zero_config = DeepSpeedZeroConfig(**d.get(C.ZERO_OPTIMIZATION, {}))
        mesh_raw = d.get(C.MESH, {})
        self.activation_checkpointing_config = ActivationCheckpointingConfig(
            **d.get("activation_checkpointing", {}))
        # only an explicit enabled/policy key drives model reconfiguration in
        # the engine; parity-boilerplate sections carrying only the
        # reference's fields (partition_activations etc.) stay parse-only, so
        # existing configs don't silently flip remat on
        _ac = d.get("activation_checkpointing", {})
        self.activation_checkpointing_explicit = (
            "enabled" in _ac or "policy" in _ac)
        self.comms_config = CommsLoggerConfig(**d.get("comms_logger", {}))
        self.flops_profiler_config = FlopsProfilerConfig(**d.get("flops_profiler", {}))
        self.monitor_config = MonitorConfig(
            tensorboard=d.get("tensorboard", {}),
            wandb=d.get("wandb", {}),
            csv_monitor=d.get("csv_monitor", {}),
        )
        self.checkpoint_config = CheckpointConfig(**d.get(C.CHECKPOINT, {}))
        self.nebula_config = NebulaConfig(**d.get("nebula", {}))
        self.data_types_config = DataTypesConfig(**d.get(C.DATA_TYPES, {}))
        # --- live tuned-config artifact (``tuning`` block) ---
        # loaded BEFORE the sections it feeds parse, so precedence is
        # uniform: a key the user wrote in this config wins, a key only
        # the artifact carries fills in, everything else defaults
        self.tuning_config = TuningConfig(**d.get("tuning", {}))
        self.tuned_artifact = None
        self.tuned_ops: Dict[str, Any] = {}
        cq_raw = d.get("comm_quantization", {})
        if self.tuning_config.enabled:
            from deepspeed_tpu.autotuning.artifact import (apply_section,
                                                           load_for_config,
                                                           ops_choices)

            try:
                # shared consumption entry point (inference uses the
                # same one): missing-artifact guidance + the loud,
                # structured fingerprint gate live in exactly one place
                self.tuned_artifact = load_for_config(
                    {"artifact": self.tuning_config.artifact,
                     "results_dir": self.tuning_config.results_dir})
            except FileNotFoundError as e:
                raise DeepSpeedConfigError(str(e))
            # the comm.tier axis owns the section's `enabled` decision
            # (its grid measured the machinery-off default too, so
            # enabling here is a MEASURED choice — see
            # artifact._expand_section_target); a bucket-bytes-only
            # artifact fills bucket_bytes without flipping the section
            # on, and an explicit user `enabled` key always wins
            cq_raw = apply_section(cq_raw, self.tuned_artifact,
                                   "comm_quantization")
            # measured mesh factorization (the autotuner's mesh.shape
            # axis): the (data, fsdp, tp) triple was measured as a UNIT,
            # so it applies only when the user pinned no axis at all —
            # mixing a user-pinned axis with two tuned ones would run a
            # factorization nobody measured
            if not any(k in mesh_raw for k in
                       ("data", "fsdp", "tp", "model", "pipe", "expert",
                        "seq")):
                mesh_raw = apply_section(mesh_raw, self.tuned_artifact,
                                         "mesh")
            # Pallas tile choices: the engine installs these into the
            # kernel-default registry at build (and removes them at
            # destroy) — kernels resolve explicit arg > tuned > default
            self.tuned_ops = ops_choices(self.tuned_artifact)
        self.comm_quantization = CommQuantizationConfig(**cq_raw)
        self.mesh = MeshConfig(**mesh_raw)
        self.pipeline_config = PipelineConfig(**d.get("pipeline", {}))
        self.telemetry_config = TelemetryConfig(**d.get("telemetry", {}))
        self.resilience_config = ResilienceConfig(**d.get("resilience", {}))
        self.aot_config = AOTConfig(**d.get("aot", {}))
        if self.aot_config.enabled and not (
                self.telemetry_config.enabled
                and (self.telemetry_config.compile_watchdog
                     or self.telemetry_config.hlo_cost)):
            raise DeepSpeedConfigError(
                "aot.enabled requires telemetry.enabled (with the "
                "compile_watchdog or hlo_cost collector on): the "
                "telemetry WatchedFunction layer is what holds the "
                "compiled executables the AOT bundle ships")

        if self.fp16.enabled and self.bf16.enabled:
            raise DeepSpeedConfigError("fp16 and bf16 cannot both be enabled")

        # --- scalars ---
        self.optimizer_name = None
        self.optimizer_params = None
        self.optimizer_legacy_fusion = False
        opt = d.get(C.OPTIMIZER)
        if opt:
            self.optimizer_name = opt.get(C.TYPE)
            if self.optimizer_name:
                self.optimizer_name = self.optimizer_name.lower()
            self.optimizer_params = opt.get(C.OPTIMIZER_PARAMS, {})
            self.optimizer_legacy_fusion = opt.get(C.LEGACY_FUSION, False)
        sched = d.get(C.SCHEDULER)
        self.scheduler_name = sched.get(C.TYPE) if sched else None
        self.scheduler_params = sched.get(C.SCHEDULER_PARAMS, {}) if sched else None

        self.zero_allow_untested_optimizer = d.get(
            C.ZERO_ALLOW_UNTESTED_OPTIMIZER, C.ZERO_ALLOW_UNTESTED_OPTIMIZER_DEFAULT)
        self.steps_per_print = d.get(C.STEPS_PER_PRINT, C.STEPS_PER_PRINT_DEFAULT)
        self.dump_state = d.get(C.DUMP_STATE, C.DUMP_STATE_DEFAULT)
        self.disable_allgather = d.get(C.DISABLE_ALLGATHER, C.DISABLE_ALLGATHER_DEFAULT)
        self.gradient_predivide_factor = d.get(
            C.GRADIENT_PREDIVIDE_FACTOR, C.GRADIENT_PREDIVIDE_FACTOR_DEFAULT)
        self.prescale_gradients = d.get(C.PRESCALE_GRADIENTS, C.PRESCALE_GRADIENTS_DEFAULT)
        self.gradient_clipping = d.get(C.GRADIENT_CLIPPING, C.GRADIENT_CLIPPING_DEFAULT)
        self.communication_data_type = d.get(
            C.COMMUNICATION_DATA_TYPE, C.COMMUNICATION_DATA_TYPE_DEFAULT)
        self.sparse_gradients_enabled = d.get(C.SPARSE_GRADIENTS, C.SPARSE_GRADIENTS_DEFAULT)
        self.wall_clock_breakdown = d.get(C.WALL_CLOCK_BREAKDOWN, C.WALL_CLOCK_BREAKDOWN_DEFAULT)
        self.memory_breakdown = d.get(C.MEMORY_BREAKDOWN, C.MEMORY_BREAKDOWN_DEFAULT)
        self.dataloader_drop_last = d.get(C.DATALOADER_DROP_LAST, C.DATALOADER_DROP_LAST_DEFAULT)
        # fuse forward+backward+optimizer into ONE compiled program when
        # gradient_accumulation_steps == 1 (no grad-accumulation buffer
        # round-trip, one dispatch per step). Requires the canonical
        # forward→backward→step call order per batch — hence opt-in.
        self.fused_step = d.get("fused_step", False)

        self.pld_enabled = d.get(C.PLD, {}).get(C.PLD_ENABLED, C.PLD_ENABLED_DEFAULT)
        self.pld_params = d.get(C.PLD, {}) if self.pld_enabled else False
        self.curriculum_enabled_legacy = d.get(C.CURRICULUM_LEARNING, {}).get(
            C.CURRICULUM_ENABLED, C.CURRICULUM_ENABLED_DEFAULT)
        self.curriculum_params_legacy = d.get(C.CURRICULUM_LEARNING, {})
        self.data_efficiency_config = d.get(C.DATA_EFFICIENCY, {})

        self.eigenvalue_enabled = d.get(C.EIGENVALUE, {}).get(
            C.EIGENVALUE_ENABLED, C.EIGENVALUE_ENABLED_DEFAULT)
        self.eigenvalue_params = d.get(C.EIGENVALUE, {})
        self.sparse_attention = d.get(C.SPARSE_ATTENTION)
        self.autotuning_config = d.get(C.AUTOTUNING, {})
        # TP policy selection (reference: injection_policy / replace_policy);
        # TP *degree* comes from mesh.tp
        self.tensor_parallel_config = d.get("tensor_parallel", {})
        self.elasticity_config = d.get(C.ELASTICITY, {})
        self.compression_config = d.get("compression_training", {})
        self.aio_config = d.get("aio", {})

        # --- batch triangle ---
        if world_size is None:
            if mpu is not None:
                world_size = mpu.get_data_parallel_world_size()
            else:
                # Data-parallel world = devices not consumed by
                # tp/pipe/seq/fsdp (fsdp shards weights, not the batch —
                # SpecLayout.batch_axes). (The expert axis folds into data
                # for batch purposes: ep <= dp, as in the reference's
                # expert+data group factory.)
                non_data = (self.mesh.tp * self.mesh.pipe * self.mesh.seq
                            * self.mesh.fsdp)
                world_size = int(os.environ.get("WORLD_SIZE", 1)) // max(1, non_data)
                world_size = max(1, world_size)
        self.world_size = world_size
        tb = d.get(C.TRAIN_BATCH_SIZE)
        mb = d.get(C.TRAIN_MICRO_BATCH_SIZE_PER_GPU)
        gas = d.get(C.GRADIENT_ACCUMULATION_STEPS)
        tb = None if tb == "auto" else tb
        mb = None if mb == "auto" else mb
        gas = None if gas == "auto" else gas
        (self.train_batch_size, self.train_micro_batch_size_per_gpu,
         self.gradient_accumulation_steps) = _resolve_batch_triangle(tb, mb, gas, world_size)

        # checkpoint knobs (flattened accessors used by the engine)
        self.checkpoint_tag_validation_enabled = self.checkpoint_config.tag_validation != "Ignore"
        self.checkpoint_tag_validation_fail = self.checkpoint_config.tag_validation == "Fail"
        self.load_universal_checkpoint = self.checkpoint_config.load_universal
        self.use_node_local_storage = self.checkpoint_config.use_node_local_storage

    # ------------------------------------------------------------------
    @property
    def tuned_artifact_hash(self) -> str:
        """Identity of the tuned config this engine was built under —
        one component of the AOT bundle cache key ("none" untuned)."""
        from deepspeed_tpu.autotuning.artifact import artifact_hash

        return artifact_hash(self.tuned_artifact)

    @property
    def zero_enabled(self) -> bool:
        return self.zero_config.stage > 0

    @property
    def zero_optimization_stage(self) -> int:
        return self.zero_config.stage

    @property
    def precision_dtype(self):
        import jax.numpy as jnp

        if self.bf16.enabled:
            return jnp.bfloat16
        if self.fp16.enabled:
            return jnp.float16
        return jnp.float32

    def print_user_config(self):
        logger.info("  json = {}".format(
            json.dumps(self._param_dict, sort_keys=True, indent=4, default=str)))

    def print(self, name):
        logger.info(f"{name}:")
        for key in sorted(self.__dict__):
            if key != "_param_dict":
                logger.info(f"  {key} {self.__dict__[key]}")
        self.print_user_config()
