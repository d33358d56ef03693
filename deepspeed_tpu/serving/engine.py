"""Serving runtime: continuous batching over a paged KV cache.

Wraps an :class:`~deepspeed_tpu.inference.engine.InferenceEngine` (its
params, sharding, dtype/quantization and telemetry/resilience managers)
with a request-level scheduler and a small FIXED set of compiled
programs:

- ``serving.prefill[T=b]`` — one per prompt bucket ``b`` (a small fixed
  set, powers of two by default): right-pads the prompt to the bucket,
  scatters its KV into the sequence's pool blocks (pad tail into the
  garbage block) and returns the first sampled token;
- ``serving.decode[slots=N]`` — ONE program for the fixed slot batch:
  every active sequence advances one token against its own block table
  and length; idle slots compute into the garbage block and are ignored;
- ``serving.decode_feed`` — the decode program's token input, made on
  the device: the newest decode output's sampled tokens with the host's
  token laid over the rows that went live since. The decode loop runs
  ONE STEP AHEAD (``_decode_step``): step N+1 is on the device's queue
  before step N's tokens are fetched, so the host's whole round (fetch,
  emit, the stream callbacks, the next schedule pass and dispatch) runs
  while the device computes. A fetched row is delivered only to the
  request it was dispatched for; an ending known by the count of tokens
  is left out of the step ahead, so only ``eos``, a cancel or a deadline
  ever costs a wasted row. No knob: off only where a proposer is
  configured (the verify step needs the tokens on the host);
- ``serving.chunk[T=c]`` — the serving fast path's third program
  (compiled only when ``prefill_chunk_tokens`` or ``prefix_cache`` is
  on): writes ``c`` prompt tokens at the sequence's current length and
  attends them against the pool — the program behind both *chunked
  prefill* (long prompts advance one budgeted chunk per step instead of
  monopolizing a whole-prompt program, collapsing the bucket ladder to
  one shape) and *prefix-cache tail prefill* (a request whose prompt
  prefix is already pooled writes only the unmatched tail);
- ``serving.cow`` — copy one pool block's rows to another (every cache
  leaf, scales included): the device half of partial-tail copy-on-write;
- ``serving.verify[slots=N,k=K]`` — speculative decoding's whole device
  surface (compiled only when ``serving.speculative`` is on, REPLACING
  the decode program in the step loop): every slot advances ``K + 1``
  query rows — the pending last token plus up to ``K`` host-proposed
  draft tokens, right-padded against the garbage block — through the
  multi-query-row paged attention kernel in ONE dispatch, returning the
  target model's greedy token at every row. The host keeps the longest
  proposal prefix the greedy oracle agrees with (1 to K+1 tokens per
  step for one dispatch), commits the accepted extent through the block
  manager's speculative ledger, and drops the rejected tail without
  copies — rejected rows sit past the committed length, masked out of
  every later attention window and overwritten by the next step's
  writes. Greedy output is bit-identical to non-speculative decode.

Finished sequences are evicted and queued requests spliced into free
slots *between* decode steps — shapes never change, so the steady-state
retrace count is zero (pinned by the telemetry compile watchdog in
``tests/unit/test_serving.py``). Greedy tokens bit-match per-request
``generate()`` output: the paged decode gathers pool blocks back into
logical order, so the math matches the dense append-cache program
term for term. With ``prefix_cache`` on, a request admitting behind an
identical system prompt maps those blocks read-only (a
:class:`~deepspeed_tpu.serving.blocks.BlockManager` refcount bump) and
prefills only its tail; with ``kv_cache_dtype: "int8"`` the pools store
per-row-quantized KV at a quarter of the bytes. All three knobs default
off, and off means byte-identical compiled programs.

Every host phase of the step loop sits in ONE kind of bracket
(``telemetry/tracing.py`` ``Brackets``): ``ds.serve.step`` around a
scheduler iteration, ``.schedule``, ``.prefill`` and ``.decode`` (each
with ``.dispatch`` and ``.sync`` children) and ``.emit`` inside it. The
bracket always opens a profiler annotation of that name, adds its
elapsed time to an always-on ledger (``stats()["phase_seconds"]``), and
emits the JSONL span under ``telemetry.tracing``. Each request
snapshots the ledger when it goes live and when it finishes, so its
record says how much of its decode life went to decode programs, to
other requests' prefills and to the host loop.

Per-request telemetry (kind ``serving``: TTFT, queue wait, tokens/s,
shed) rides the unified event stream; the resilience hang watchdog sees
begin/heartbeat/abandon brackets so a wedged decode collective is a
detected stall while an idle server is never judged hung.
"""

import collections
import heapq
import time
from typing import Dict, List, Optional

import numpy as np

from deepspeed_tpu.runtime.resilience.chaos import raise_if
from deepspeed_tpu.serving.blocks import BlockManager
from deepspeed_tpu.serving.config import (ServingConfig, blocks_for_tokens,
                                          bucket_for, resolve_buckets)
from deepspeed_tpu.serving.prefix_cache import PrefixCache
from deepspeed_tpu.serving.request import FINISHED, Request
from deepspeed_tpu.serving.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.serving.spec_decode import build_proposer
from deepspeed_tpu.telemetry import process_ledger
from deepspeed_tpu.telemetry.manager import (constructor_bracket,
                                             startup_bracket)
from deepspeed_tpu.telemetry.tracing import StepTrace, end_span, to_ns
from deepspeed_tpu.utils.logging import log_dist, logger


# the bracketed phases that tile a scheduler iteration: the ledger's keys
_PHASES = ("schedule", "prefill", "decode", "emit")
# what a slow step's row says of itself: the phases, and inside prefill and
# decode the staging and program call against the wait for the tokens
_STEP_PARTS = _PHASES + ("dispatch", "sync")

# the slowest steps of the stats window that ``stats()["slow_steps"]``
# keeps, and the wall time from which a step says so in the log
SLOW_STEPS_KEPT = 8
SLOW_STEP_LOG_MS = 250.0

# an idle slot's row of the keyed sampler's five arrays (seed, flag,
# temperature, top-k, top-p)
_IDLE_SAMP = (0, 0, 1.0, 0, 0.0)

# one decode step on the device's queue, its tokens not fetched yet: the
# program's output (still on the device), the (slot, request) pairs it was
# dispatched for, and the lengths it was dispatched with
# (``selected``: the step's chosen keys, still on the device, in a list
# that is empty where the model's programs return none)
_Flight = collections.namedtuple("_Flight", "toks pairs lengths selected")
# finished requests whose chosen keys are kept (``keep_selected``): a
# 30,000-token request's are 0.6 GB on the host
SELECTED_KEYS_KEPT = 8


def _model_window(model_config) -> Optional[int]:
    return (getattr(model_config, "n_positions", None)
            or getattr(model_config, "max_position_embeddings", None))


class ServingEngine(process_ledger.FirstCalls):
    @constructor_bracket("serving_init", span="startup.serving_init")
    def __init__(self, model_or_engine, config=None, draft_model=None,
                 clock=time.monotonic, **kwargs):
        import jax
        import jax.numpy as jnp

        from deepspeed_tpu.inference.engine import InferenceEngine
        from deepspeed_tpu.runtime.config import DeepSpeedConfigError

        self._jax, self._jnp = jax, jnp
        # injectable timebase: every request timestamp, deadline sweep
        # and span bracket reads THIS clock, so the trace-replay harness
        # can drive a real engine faster than real time (the router and
        # fleet manager share the same seam)
        self.clock = clock
        if isinstance(model_or_engine, InferenceEngine):
            if config is not None or kwargs:
                raise ValueError(
                    "pass config/kwargs to the InferenceEngine, not again "
                    "to ServingEngine when wrapping one")
            self.engine = model_or_engine
            self._owns_engine = False
        else:
            self.engine = InferenceEngine(model_or_engine, config=config,
                                          **kwargs)
            self._owns_engine = True
        scfg = self.engine._serving_cfg
        if scfg is None or not scfg.enabled:
            raise DeepSpeedConfigError(
                "ServingEngine needs a `serving` block in the inference "
                'config, e.g. init_inference(model, serving={"block_size": '
                '16, "decode_slots": 4})')
        self.config: ServingConfig = scfg

        mcfg = self.engine.model_config
        if mcfg is None or not hasattr(mcfg, "for_paged_decode"):
            raise ValueError(
                "serving needs a model whose config provides "
                "for_paged_decode() — the canonical decoder family "
                "(GPT2LMHeadModel and its OPT/BLOOM/GPT-J/NeoX variants)")
        window = _model_window(mcfg)
        self.max_len = int(self.config.max_model_len or window or 1024)
        if window:
            self.max_len = min(self.max_len, int(window))
        bs = self.config.block_size
        self.blocks_per_seq = blocks_for_tokens(self.max_len, bs)
        # garbage block + conservative worst-case reservation per slot:
        # admission never admits work the pool cannot finish
        self.num_blocks = int(self.config.num_blocks) or (
            1 + self.config.decode_slots * self.blocks_per_seq)
        self.buckets = resolve_buckets(self.config.prompt_buckets,
                                       self.max_len, floor=bs)
        # what a served model's config and module say of themselves, and
        # what each probe below means, is written once: the docstrings of
        # ``ServedConfig`` and ``PagedDecoder`` in models/blocks.py. Here:
        # state of FIXED SIZE a decode slot keeps beside its block table
        # (a ring of blocks, a row of a state pool), and a block pool whose
        # rows are NOT keys and values by heads (a latent row a token); the
        # mechanisms that cannot carry either refuse the model by ``what``
        state_for = getattr(mcfg, "paged_slot_state_for", None)
        self.slot_state = (state_for(bs) if state_for else None) or None
        self.slot_entries = (int(self.slot_state["entries"])
                             if self.slot_state else 0)
        row_kind = getattr(mcfg, "paged_row_kind", None)
        self.row_kind = (row_kind() if row_kind else None) or None
        # keywords omitted on purpose: a model family predating a knob
        # keeps serving exactly as before
        knobs = {}
        if self.slot_state or self.row_kind:
            self._refuse_beside_slot_state()
        if self.slot_state:
            knobs[self.slot_state["knob"]] = self.config.decode_slots
        if self.config.kv_cache_dtype:
            knobs["kv_dtype"] = self.config.kv_cache_dtype
        # request id -> int32 [tokens processed, sparse layers x k], the
        # oldest dropped past ``routed_experts_kept``
        self._routed_kept: Dict[str, np.ndarray] = {}
        if self.config.routed_experts_kept:
            if not getattr(type(self.engine.module), "serve_routed", False):
                from deepspeed_tpu.runtime.config import DeepSpeedConfigError

                raise DeepSpeedConfigError(
                    "serving.routed_experts_kept: "
                    f"{type(self.engine.module).__name__}'s serving "
                    "programs return no routed experts")
            knobs["return_routed"] = True
        # request id -> uint32 [tokens processed, layers, words]: the keys
        # each query chose, for the requests that asked
        # (``submit(.., keep_selected=True)``), the oldest dropped past
        # ``SELECTED_KEYS_KEPT``. A model whose programs return the sets
        # returns them always (``serve_selected``); they stay on the device
        # unless a request asks
        self._selected_kept: Dict[str, np.ndarray] = {}
        dcfg = mcfg.for_paged_decode(self.num_blocks, bs, **knobs)
        self._routed_width = (dcfg.routed_width
                              if "return_routed" in knobs else 0)
        self._dmodule = type(self.engine.module)(dcfg)
        # the parameter the model looks its tokens up in, where it names one
        self._lookup_table = getattr(type(self.engine.module),
                                     "lookup_table", None)
        self.block_mgr = BlockManager(self.num_blocks, bs,
                                      self.blocks_per_seq)
        self.prefix = (PrefixCache(self.block_mgr)
                       if self.config.prefix_cache else None)
        self.telemetry = self.engine.telemetry
        self.resilience = self.engine.resilience
        # span tracer (inert unless telemetry.tracing is on): request
        # traces — queue/prefill/cow/decode legs — ride the event stream
        self._tracer = self.telemetry.tracer
        # the always-on ledger: cumulative seconds inside the four
        # phases that tile a scheduler iteration (the brackets add to
        # them), plus the counts taken at the same boundaries. Plain
        # Python numbers, no device work. Never reset (each request
        # holds two snapshots of it); stats() reports it against the
        # base reset_stats() takes, the registry gets what it gained
        # since the last publish.
        self._ledger = {**dict.fromkeys(_PHASES, 0.0), "step": 0.0,
                        # inside prefill and decode: staging and the
                        # program call; the wait for its tokens
                        "dispatch": 0.0, "sync": 0.0,
                        "prefill_calls": 0, "busy_slot_steps": 0,
                        # decode steps dispatched while another was still
                        # in flight, and rows fetched and not delivered
                        "decode_ahead_steps": 0, "decode_dropped_rows": 0}
        # what the model itself counts in a call (a sparse model: experts
        # touched, pairs routed here and in all), handed back behind the
        # sampled tokens and summed here by the kind of program; and the
        # bytes of per-sequence state live at each decode step, by kind
        # (rows a block table addresses, a ring's, a convolution's state),
        # where the model says what its busy rows keep (``kv_live_bytes``)
        self._counter_names = tuple(getattr(type(self.engine.module),
                                            "serve_counters", ()))
        for phase in ("prefill", "decode"):
            for name in self._counter_names:
                self._ledger[f"{phase}.{name}"] = 0
        kv_bytes = getattr(dcfg, "kv_bytes_per_token", None)
        self._kv_bytes = kv_bytes() if kv_bytes else None
        self._live_bytes = getattr(dcfg, "kv_live_bytes", None)
        self._kv_kinds = (tuple(self._live_bytes(np.zeros((0,), np.int64)))
                          if self._live_bytes else ())
        for kind in self._kv_kinds:
            self._ledger[f"kv_live_bytes.{kind}"] = 0
        self._ledger_base = dict(self._ledger)
        self._ledger_published = dict(self._ledger)
        self._busy = 0  # active slots of the latest decode step
        # the slowest steps of the stats window (``_note_step``): a heap of
        # (wall + seam, count, row); and where the last step ended: its
        # end, the process's idle, collector seconds and first calls as
        # they stood then, and whether it left work behind
        self._slow: list = []
        self._gc_base = process_ledger.LEDGER.host_pauses()
        self._gc_published = process_ledger.LEDGER.gc["pause_secs"]
        self._step_calls = 0
        self._last_end = (0.0, 0.0, self._gc_published,
                          process_ledger.LEDGER.first_calls, False)
        # THE bracket around every host phase of the step loop
        # (telemetry/tracing.py Brackets): ds.serve.<phase> on the
        # profiler's clock always, the JSONL span under tracing, the
        # ledger above from the injected clock
        self._step_trace = StepTrace(self._tracer, root="serve_step")
        self._bracket = self.telemetry.brackets(
            "serve", clock=self.clock, ledger=self._ledger,
            step_trace=self._step_trace)
        self.sched = ContinuousBatchingScheduler(
            self.config, self.block_mgr, self.max_len, self.buckets,
            clock=self.clock, prefix_cache=self.prefix,
            tracer=self._tracer)

        with startup_bracket("pool", span="startup.pool"):
            self.cache = self._init_cache()
        self._tables = np.full(
            (self.config.decode_slots,
             self.blocks_per_seq + self.slot_entries), 0, np.int32)
        self._last_tokens = np.zeros((self.config.decode_slots,), np.int32)
        self._lengths = np.zeros((self.config.decode_slots,), np.int32)
        self._prefill_fns: Dict[int, object] = {}
        self._decode_fn = self._decode_py = self._decode_asked = None
        # the decode loop runs one step ahead (``_decode_step``): the step
        # in flight, the newest decode output (the next step's tokens, on
        # the device), the program that lays the host's tokens over it,
        # and what a flush outside ``step()`` finished
        self._flight: Optional[_Flight] = None
        self._feed_fn = None
        self._prev_toks = None
        self._late: List[Request] = []
        # chunked / prefix-continued prefill state: a slot mid-prefill is
        # NOT in the decode batch (its row of self._tables stays pointed
        # at the garbage block) until its whole prompt is written
        self.chunk_tokens = int(self.config.prefill_chunk_tokens)
        self._prefilling: Dict[int, Request] = {}
        self._pf_tables: Dict[int, np.ndarray] = {}
        self._pf_pos: Dict[int, int] = {}
        self._pf_next = 0  # round-robin cursor over prefilling slots
        self._chunk_fns: Dict[int, object] = {}
        self._cow_fn = None
        # live KV migration import programs, one per covered-block count
        # (built lazily — a fleet that never migrates compiles nothing)
        self._migrate_fns: Dict[int, object] = {}
        # speculative decoding: host-side proposer + the ONE compiled
        # k-token verify program (replaces the decode program in the
        # step loop; None => the decode path is exactly as before)
        self._proposer = build_proposer(self.config.speculative,
                                        draft_model=draft_model)
        self.spec_k = (int(self.config.speculative.num_speculative_tokens)
                       if self._proposer is not None else 0)
        self._verify_fn = None
        self._rng = jax.random.PRNGKey(self.config.seed)
        # reproducible keyed sampling (serving.sampling): per-slot
        # sampling state rides the compiled programs as traced arrays —
        # the key for request R's token at position P folds (R's seed, P)
        # inside the program, so the emitted token is independent of slot
        # index, batch composition and tp layout. With the block absent
        # these arrays do not exist and every program is byte-identical.
        self._keyed = bool(self.config.sampling
                           and self.config.sampling.enabled)
        if self._keyed:
            n = self.config.decode_slots
            self._seeds = np.zeros((n,), np.uint32)
            self._samp_on = np.zeros((n,), np.int32)
            self._temps = np.ones((n,), np.float32)
            self._top_ks = np.zeros((n,), np.int32)
            self._top_ps = np.zeros((n,), np.float32)
        self._step_count = 0
        # steps by who wrote their KV rows (``_kv_write_form``), and the
        # model's answer for a step of each width, asked once
        self._kv_write = {"kernel": 0, "scatter": 0}
        self._kv_most: Dict[int, Optional[int]] = {}
        # speculation counters over the stats window (reset_stats zeroes
        # them WITH the records deque — the bounded records alone would
        # decay any per-step ratio on a long-running server)
        self._spec_steps = 0
        self._window_draft_tokens = 0
        self._window_accepted_tokens = 0
        # prefix-cache window counters (the hit-rate GAUGE's input —
        # recomputing from the bounded records deque per step would both
        # cost a scan and decay on long runs)
        self._window_prompt_tokens = 0
        self._window_hit_tokens = 0
        self._finished_count = 0
        # live metrics plane: the telemetry manager's registry (the
        # inert NULL_REGISTRY unless telemetry.metrics_port/metrics_file
        # armed it), so every instrumentation site runs unconditional
        self._metrics = self.telemetry.metrics
        # bounded retention (a long-running server must not accumulate a
        # dead Request per served request until OOM — same contract as
        # the telemetry manager's bounded event tail); stats() percentiles
        # therefore cover the most recent window
        self.finished = collections.deque(maxlen=1024)
        self.records = collections.deque(maxlen=4096)
        log_dist(
            f"ServingEngine: slots={self.config.decode_slots} "
            f"block_size={bs} num_blocks={self.num_blocks} "
            f"buckets={self.buckets} max_len={self.max_len}", ranks=[0])
        # where the weights lie: asked of the decode program and placed
        # once, before any program is built (``stats()["weight_layouts"]``)
        with startup_bracket("weight_layouts",
                             span="startup.weight_layouts") as ph:
            self._weight_layouts = self._lay_out_weights(ph)

    # ------------------------------------------------------------------
    def _init_cache(self):
        """The zeroed KV pool, shaped by tracing the paged decode module's
        init without running it (eval_shape: no compute, no params
        materialized). Every leaf has the pool's one shape, ``[layers,
        blocks, block_size, lanes]`` (``ops/decode_attention.py``: K/V
        rows of ``heads * head_dim`` lanes, int8 scale rows a lane a head),
        which every serving program writes and reads in place. Placed
        with the mesh shardings the compiled programs emit —
        ``decode_cache_specs``: on a tp>1 mesh the lane axis is split
        over the tp axis into ``heads / tp`` contiguous heads a shard, a
        per-shard KV pool per device group, exactly the layout the
        TP-aware paged Pallas kernel consumes — so the FIRST prefill's
        argument signature already matches steady state (a `jnp.zeros`
        pool would carry SingleDeviceSharding and cost that bucket one
        spurious retrace)."""
        jax, jnp = self._jax, self._jnp
        from deepspeed_tpu.module_inject.policies import decode_cache_specs

        pg = {"block_tables": jnp.zeros(
                  (1, self.blocks_per_seq + self.slot_entries), jnp.int32),
              "lengths": jnp.zeros((1,), jnp.int32),
              "num_valid": jnp.zeros((1,), jnp.int32), "prefill": True}
        shapes = jax.eval_shape(
            lambda: self._dmodule.init(jax.random.PRNGKey(0),
                                       jnp.zeros((1, 1), jnp.int32),
                                       paging=pg))
        shardings = decode_cache_specs(shapes["cache"], self.engine.mesh,
                                       heads=self._dmodule.config.n_head)
        return jax.tree_util.tree_map(
            lambda s, sh: jax.device_put(jnp.zeros(s.shape, s.dtype), sh),
            shapes["cache"], shardings)

    def _beside_slot_state(self, mechanism: str) -> str:
        """Why ``mechanism`` cannot serve this model: it moves, shares or
        rewrites rows of keys and values by heads that a block table
        addresses, and knows nothing of the state a slot keeps beside the
        table (a ring, a convolution's state) or of a row that is no keys
        and values by heads (a latent row): the model's own words."""
        name = type(self.engine.module).__name__
        if self.slot_state:
            return (f"{mechanism} cannot serve {name}: its "
                    f"{self.slot_state['what']}, beside the block table, "
                    f"and {mechanism} handles only the rows a block table "
                    "addresses")
        return (f"{mechanism} cannot serve {name}: its "
                f"{self.row_kind['what']}, and {mechanism} handles only "
                "rows of keys and values by heads")

    def _refuse_beside_slot_state(self):
        """A model that keeps state a decode slot beside the block table
        (``self.slot_state``) has a second kind of per-sequence state, and
        these mechanisms know one: each refuses the model here, by name,
        until it is taught the second; and so each does a model whose rows
        are no keys and values by heads (``self.row_kind``), which none of
        them has met. (Live migration refuses at its calls:
        ``export_sequence`` / ``import_sequence``.)"""
        from deepspeed_tpu.runtime.config import DeepSpeedConfigError

        asked = {
            "serving.prefix_cache": self.config.prefix_cache,
            "serving.speculative": self.config.speculative is not None
            and self.config.speculative.enabled,
            "serving.kv_cache_dtype": bool(self.config.kv_cache_dtype),
            "tensor_parallel.tp_size > 1": self.engine.mp_world_size > 1,
        }
        for mechanism, on in asked.items():
            if on:
                raise DeepSpeedConfigError(self._beside_slot_state(mechanism))

    def _slot_table(self, slot: int, table: np.ndarray) -> np.ndarray:
        """The table a slot's programs get: the sequence's blocks and,
        for a model that keeps state a slot, the slot's own entries of
        that state's pool: slot ``s`` has ``1 + s * entries ..`` (a ring's
        blocks of the window pool; the one row of the convolutions' state
        pool; for a model that keeps both, the ring's blocks and then the
        state's row, each counted in its own pool), 0 being the pool's
        garbage block or row, which an idle row's zeroed table names."""
        if not self.slot_entries:
            return table
        # (a slot that keeps two kinds of state says its ``parts``: each
        # counts its own pool from 1)
        own = [1 + slot * part + np.arange(part, dtype=np.int32)
               for part in self.slot_state.get("parts",
                                               (self.slot_entries,))]
        return np.concatenate([table.astype(np.int32), *own])

    def _with_counters(self, tok, out):
        """The sampled tokens and, behind them in the same array, what the
        model counted in this call (``(logits, {"counters": int32[n]})``):
        the host's one fetch a step brings both."""
        jnp = self._jnp
        aux = out[1] if isinstance(out, tuple) and len(out) > 1 else None
        if isinstance(aux, dict) and "counters" in aux:
            # (and behind those, where the model was asked for them, the
            # experts each row's tokens chose)
            return jnp.concatenate(
                [tok.astype(jnp.int32), aux["counters"].astype(jnp.int32)]
                + ([aux["routed"].astype(jnp.int32).reshape(-1)]
                   if "routed" in aux else []))
        return tok

    def _count(self, phase: str, counted):
        for name, n in zip(self._counter_names, counted):
            self._ledger[f"{phase}.{name}"] += int(n)

    def _routed(self, fetched, rows: int) -> Optional[np.ndarray]:
        """What lies behind the tokens and the counters of a fetched
        array: ``[rows, tokens a row, layers x k]``, or None."""
        tail = fetched[rows + len(self._counter_names):]
        return tail.reshape(rows, -1, self._routed_width) if tail.size \
            else None

    def _returns(self, tok, out, cache):
        """What a serving program returns: the tokens (with the counters
        behind them) and the pool; and, from a model whose calls hand back
        the keys they chose, those, which stay on the device unless a
        request asked for them."""
        head = (self._with_counters(tok, out), cache)
        aux = out[1] if isinstance(out, tuple) and len(out) > 1 else None
        if isinstance(aux, dict) and "selected" in aux:
            return head + (aux["selected"],)
        return head

    def _keep_selected(self, req: Request, selected, row: int, tokens: int):
        """Fetch the chosen keys of ``tokens`` queries of one row of a
        call, for a request that asked."""
        if selected and req.keep_selected:
            req.selected.append(np.asarray(selected[0][row, :tokens]))

    def selected_keys(self, request_id: str) -> Optional[np.ndarray]:
        """``uint32 [tokens, layers, words]``: the keys each query the
        programs processed for a finished request chose, a layer (key ``j``
        bit ``j % 32`` of word ``j // 32``); None for a request that did not
        ask (``keep_selected``) or is not among the last
        ``SELECTED_KEYS_KEPT`` that did."""
        return self._selected_kept.get(request_id)

    def routed_experts(self, request_id: str) -> Optional[np.ndarray]:
        """``int32 [tokens, sparse layers x k]``: the experts each token
        the programs processed for a finished request chose (the prompt,
        then every served token but the last, which was never fed back),
        layer by layer; None for a request not among the last
        ``serving.routed_experts_kept`` to finish."""
        return self._routed_kept.get(request_id)

    def _donate(self, argnum: int = 1):
        # the old pool is dead after every call — donate it so steady-state
        # serving holds ONE pool allocation (CPU jax warns instead of
        # donating; skip there)
        return (argnum,) if self._jax.default_backend() != "cpu" else ()

    def _jit(self, fn, program: str, watch: str, donate: int = 1):
        """``jax.jit`` of one serving program under its own name: the
        compiled module (and so the profiler's program lane and the HLO
        dump) reads ``jit_<program>`` instead of ``jit_fn`` for all."""
        fn.__name__ = fn.__qualname__ = program
        return self.engine.telemetry.watch_jit(
            self._jax.jit(fn, donate_argnums=self._donate(donate)), watch)

    def _paging(self, ids, tables, lengths, num_valid, prefill=False):
        """The ``paging`` argument of a model call, for every program: the
        traced tables and lengths, the kind of call, and, where the model
        names the table it looks its tokens up in, the access pattern
        chosen from how that table lies on its device and from this
        program's static token count."""
        from deepspeed_tpu.models.decode_utils import lookup_form

        paging = {"block_tables": tables, "lengths": lengths,
                  "num_valid": num_valid, "prefill": prefill}
        if self._lookup_table is not None:
            # the engine's own leaf: the device array as it lies (a
            # quantised leaf is a dict, which reads as no array)
            paging["lookup"] = lookup_form(
                self.engine.params.get(self._lookup_table), ids.size)
        return paging

    def _last_row(self, logits, ids, num_valid):
        """The logits a prompt's next token is sampled from: the row at
        each sequence's LAST REAL position (right padding: index
        ``num_valid - 1``) of ``[B, T, vocab]``; a model whose paged call
        hands back that row alone (``PagedDecoder.rows_from``: ``[B, 1,
        vocab]`` for ``T > 1`` tokens) has taken it already."""
        if logits.shape[1] != ids.shape[1]:
            return logits[:, 0]
        return self._jnp.take_along_axis(
            logits, (num_valid - 1)[:, None, None], axis=1)[:, 0]

    def _sample(self, logits, rng):
        from deepspeed_tpu.inference.engine import sample_logits

        sc = self.config
        return sample_logits(logits, rng, sc.temperature, sc.do_sample,
                             sc.top_k, sc.top_p)

    def _build_prefill(self, T: int):
        jnp = self._jnp
        dmodule, dequant = self._dmodule, self.engine._dequantize
        logits_of = self.engine._logits_of
        if self._keyed:
            from deepspeed_tpu.ops.sampling import keyed_sample

            def kfn(qparams, cache, ids, tables, num_valid, seeds, flags,
                    temps, top_ks, top_ps):
                params = dequant(qparams)
                paging = self._paging(
                    ids, tables, jnp.zeros((ids.shape[0],), jnp.int32),
                    num_valid, prefill=True)
                out, vars_ = dmodule.apply(
                    {"params": params, "cache": cache}, ids,
                    mutable=["cache"], paging=paging)
                logits = logits_of(out)
                last = self._last_row(logits, ids, num_valid)
                # the first generated token's absolute position is the
                # prompt length — num_valid itself
                tok = keyed_sample(last, seeds, num_valid, flags, temps,
                                   top_ks, top_ps)
                return self._returns(tok, out, vars_["cache"])

            return self._jit(kfn, f"serving_prefill_T{T}",
                             f"serving.prefill[T={T}]")

        def fn(qparams, cache, ids, tables, num_valid, rng):
            params = dequant(qparams)
            paging = self._paging(
                ids, tables, jnp.zeros((ids.shape[0],), jnp.int32),
                num_valid, prefill=True)
            out, vars_ = dmodule.apply({"params": params, "cache": cache},
                                       ids, mutable=["cache"], paging=paging)
            logits = logits_of(out)
            # the request's next token depends on its LAST REAL position
            # (right padding: index num_valid-1)
            last = self._last_row(logits, ids, num_valid)
            return self._returns(self._sample(last, rng), out,
                                 vars_["cache"])

        return self._jit(fn, f"serving_prefill_T{T}",
                         f"serving.prefill[T={T}]")

    def _decode_program(self):
        """The decode program as a Python function, not yet jitted: what
        ``_build_decode`` compiles, and what ``_lay_out_weights`` asks how
        the weights should lie. ONE function object for both, so that the
        second ``jax.jit`` of it finds the first's trace."""
        if self._decode_py is None:
            self._decode_py = self._decode_program_of()
            self._decode_py.__name__ = self._decode_py.__qualname__ = (
                "serving_decode")
        return self._decode_py

    def _decode_program_of(self):
        jnp = self._jnp
        dmodule, dequant = self._dmodule, self.engine._dequantize
        logits_of = self.engine._logits_of
        if self._keyed:
            from deepspeed_tpu.ops.sampling import keyed_sample

            def kfn(qparams, cache, tokens, tables, lengths, seeds, flags,
                    temps, top_ks, top_ps):
                params = dequant(qparams)
                paging = self._paging(tokens, tables, lengths,
                                      jnp.ones_like(lengths))
                out, vars_ = dmodule.apply(
                    {"params": params, "cache": cache}, tokens,
                    mutable=["cache"], paging=paging)
                logits = logits_of(out)[:, -1]
                # this step emits the token at absolute position
                # lengths + 1 (lengths tokens are pooled; the pending
                # last token sits at position lengths)
                tok = keyed_sample(logits, seeds, lengths + 1, flags,
                                   temps, top_ks, top_ps)
                return self._returns(tok, out, vars_["cache"])

            return kfn

        def fn(qparams, cache, tokens, tables, lengths, rng):
            params = dequant(qparams)
            paging = self._paging(tokens, tables, lengths,
                                  jnp.ones_like(lengths))
            out, vars_ = dmodule.apply({"params": params, "cache": cache},
                                       tokens, mutable=["cache"],
                                       paging=paging)
            logits = logits_of(out)[:, -1]
            return self._returns(self._sample(logits, rng), out,
                                 vars_["cache"])

        return fn

    def _build_decode(self):
        """The decode program: where ``_lay_out_weights`` asked it how the
        weights should lie, the executable that answered (the tree lies as
        it asked: ``weight_layouts.AskedProgram``), unless telemetry
        watches compiles, which compiles and counts its own."""
        from deepspeed_tpu.serving.weight_layouts import AskedProgram
        from deepspeed_tpu.telemetry.jit_watch import WatchedFunction

        jitted = self._jit(
            self._decode_program(), "serving_decode",
            f"serving.decode[slots={self.config.decode_slots}]")
        asked, self._decode_asked = self._decode_asked, None
        if asked is None or isinstance(jitted, WatchedFunction):
            return jitted
        return AskedProgram(asked, jitted)

    def _decode_shapes(self):
        """The decode program's arguments behind the weights, as shapes:
        what ``_dispatch`` hands it (the pool as it lies, the feed's
        tokens, a table row and a length a slot, the sampler's tail)."""
        jax, jnp = self._jax, self._jnp
        from jax.sharding import NamedSharding, PartitionSpec

        n = self.config.decode_slots
        s = jax.ShapeDtypeStruct
        tail = (tuple(s((n,), t) for t in (jnp.uint32, jnp.int32, jnp.float32,
                                           jnp.int32, jnp.float32))
                if self._keyed else (s(self._rng.shape, self._rng.dtype),))
        return (self.cache,
                s((n, 1), jnp.int32, sharding=NamedSharding(
                    self.engine.mesh, PartitionSpec())),
                s(self._tables.shape, jnp.int32), s((n,), jnp.int32), *tail)

    def _asked_weight_formats(self):
        """How the decode program would have each leaf of the weights lie
        (``weight_layouts.ask``: one compile, nothing run); the compiled
        program is kept for ``_build_decode``."""
        from deepspeed_tpu.serving import weight_layouts

        formats, self._decode_asked = weight_layouts.ask(
            self._decode_program(), self.engine.params,
            self._decode_shapes(), donate=self._donate())
        return formats

    def _lay_out_weights(self, ph) -> dict:
        """Lay each weight out ONCE as the decode program asks, before any
        program is built: every program then compiles for the tree as it
        lies (``jax.jit`` with no layout of its own takes a committed
        argument's), and none re-lays a weight on every call.

        THE RULE (one for every model, read off compiled programs only): a
        leaf moves if the decode program, compiled with the layout of every
        weight left to the compiler, names another layout for it than the
        leaf has. Every leaf the decode program names, not only those it
        would copy: compiled for the described chip, no program of any
        serve cell copies more of its parameters a call over the tree so
        laid than over the default one (``tools/probe_weight_layouts.py``;
        the table is in PERF.md, PR 53). A leaf the program does not ask to
        move stays a plain array in the backend's default layout, and no
        program pins a format: a leaf replaced later by
        ``jax.device_put(x, old.sharding)`` is served without a compile.

        The values, shapes and types are untouched (``jax.device_put`` to a
        ``Format``): ``np.asarray(leaf)``, a checkpoint and a reference
        jitted over ``engine.params`` see the same arrays bit for bit.

        Not engaged, and the log line says which, where the weights are not
        plain arrays on one device (``tp_size`` > 1: a layout is a device's
        own matter and the mesh's programs were not probed; a host tree), a
        quantised tree (the program reads what ``_dequantize`` rebuilds, not
        the leaves), or a proposer (the verify program decodes). Returns
        ``stats()["weight_layouts"]``."""
        jax = self._jax
        from deepspeed_tpu.serving import weight_layouts

        flat, treedef = jax.tree_util.tree_flatten_with_path(
            self.engine.params)
        counted = dict(weight_layouts.NOT_ENGAGED, leaves=len(flat))
        why = ("a quantised tree" if self.engine._quantized else
               "the verify program decodes" if self._proposer is not None
               else "" if all(
                   isinstance(leaf, jax.Array)
                   and len(leaf.sharding.device_set) == 1
                   for _, leaf in flat) else
               f"not plain arrays on one device: tp_size "
               f"{self.engine.mp_world_size}, a mesh of "
               f"{self.engine.mesh.devices.size}")
        if why:
            log_dist(f"weights lie as they came ({why})", ranks=[0])
            return counted
        # (the seconds come from the start-up bracket ``ph`` this runs in,
        # ``ds.startup.weight_layouts``, and its clock)
        clock = process_ledger.LEDGER.clock
        with process_ledger.LEDGER.building("serving_decode"):
            asked = treedef.flatten_up_to(self._asked_weight_formats())
        t1 = clock()
        names = [weight_layouts.leaf_name(path) for path, _ in flat]
        leaves = [leaf for _, leaf in flat]
        # the list is now the tree's only owner inside the engine: a leaf's
        # old buffer goes as its new one takes its place
        del flat
        self.engine.params = None
        try:
            moved = weight_layouts.lay_out(leaves, asked)
        finally:
            self.engine.params = jax.tree_util.tree_unflatten(treedef,
                                                              leaves)
        counted.update(
            asked_by="serving_decode", leaves_moved=len(moved),
            bytes_moved=sum(int(leaves[i].nbytes) for i in moved))
        log_dist(
            f"weights laid out for serving_decode: {len(moved)} of "
            f"{len(leaves)} leaves, {counted['bytes_moved']:,} bytes, asked "
            f"in {t1 - ph.t0:.2f} s and laid in {clock() - t1:.2f} s"
            + (" (" + ", ".join(
                f"{names[i]} {tuple(leaves[i].shape)} -> "
                f"{tuple(asked[i].layout.major_to_minor)}"
                for i in moved) + ")" if moved else ""), ranks=[0])
        return counted

    def _build_feed(self):
        """The decode program's token input, made on the device: the
        newest decode output's sampled tokens, with the host's token laid
        over every row that ``fresh`` gives one (``>= 0``: a slot that
        went live since that step was dispatched, or zero for an idle
        row). So a sampled token never waits for the host to come back as
        the next step's input. Both sides carry the mesh's replicated
        sharding by declaration: the decode program then sees one
        argument signature whatever made ``prev`` (the zeros before the
        first step, returned beside the program, or the decode program's
        own output), and stays ONE program."""
        jax, jnp = self._jax, self._jnp
        from jax.sharding import NamedSharding, PartitionSpec

        n = self.config.decode_slots
        everywhere = NamedSharding(self.engine.mesh, PartitionSpec())

        def serving_decode_feed(prev, fresh):
            return jnp.where(fresh < 0, prev[:n].astype(jnp.int32),
                             fresh)[:, None]

        # what ``_with_counters`` returns for the slot batch: the tokens,
        # then the counters, then each row's routed experts
        zeros = jax.device_put(np.zeros(
            (n + len(self._counter_names) + n * self._routed_width,),
            np.int32), everywhere)
        return self.engine.telemetry.watch_jit(
            jax.jit(serving_decode_feed, in_shardings=everywhere,
                    out_shardings=everywhere), "serving.decode_feed"), zeros

    def _first_feed(self):
        """The feed program's miss path: built and, where this is its
        first call in the process, run once over its own zeros inside its
        ``program`` bracket (a call of its own: in the loop it is called
        inside the decode program's dispatch, and that program's first
        call has a bracket of its own)."""
        self._first_call("serving_decode_feed")
        self._feed_fn, self._prev_toks = self._build_feed()
        if self._first_open:
            self._jax.block_until_ready(self._feed_fn(
                self._prev_toks,
                np.full((self.config.decode_slots,), -1, np.int32)))
            self._first_result()

    def _build_chunk(self, T: int):
        """One prefill chunk: write ``num_valid`` prompt tokens at the
        sequence's current pool length and attend them against everything
        already pooled (shared prefix blocks included) plus themselves,
        causally. The sampled token at the last REAL position is
        meaningful only on the final chunk — it is the request's first
        generated token. Which rows reach the head is the model's: all
        ``T`` (``[B, T, vocab]`` logits, of which ``_last_row`` keeps the
        one at ``num_valid - 1``), or, from a model whose later layers only
        read what the earlier ones cached (``PagedDecoder.rows_from``),
        that row alone."""
        jnp = self._jnp
        dmodule, dequant = self._dmodule, self.engine._dequantize
        logits_of = self.engine._logits_of
        if self._keyed:
            from deepspeed_tpu.ops.sampling import keyed_sample

            def kfn(qparams, cache, ids, tables, lengths, num_valid,
                    seeds, flags, temps, top_ks, top_ps):
                params = dequant(qparams)
                paging = self._paging(ids, tables, lengths, num_valid)
                out, vars_ = dmodule.apply(
                    {"params": params, "cache": cache}, ids,
                    mutable=["cache"], paging=paging)
                logits = logits_of(out)
                last = self._last_row(logits, ids, num_valid)
                # only the FINAL chunk's token is consumed, at absolute
                # position lengths + num_valid = the full prompt length
                # — identical to the whole-prompt prefill's fold-in, so
                # chunked and unchunked admission sample the same token
                tok = keyed_sample(last, seeds, lengths + num_valid,
                                   flags, temps, top_ks, top_ps)
                return self._returns(tok, out, vars_["cache"])

            return self._jit(kfn, f"serving_chunk_T{T}",
                             f"serving.chunk[T={T}]")

        def fn(qparams, cache, ids, tables, lengths, num_valid, rng):
            params = dequant(qparams)
            paging = self._paging(ids, tables, lengths, num_valid)
            out, vars_ = dmodule.apply({"params": params, "cache": cache},
                                       ids, mutable=["cache"], paging=paging)
            logits = logits_of(out)
            last = self._last_row(logits, ids, num_valid)
            return self._returns(self._sample(last, rng), out,
                                 vars_["cache"])

        return self._jit(fn, f"serving_chunk_T{T}",
                         f"serving.chunk[T={T}]")

    def _build_verify(self):
        """The k-token verify program — speculative decoding's single
        compiled surface. Every slot advances ``T = k + 1`` query rows
        at once (pending last token + the proposals, right-padded), the
        multi-query-row paged attention kernel masks each row causally
        at ``lengths[b] + row``, ``num_valid`` routes pad rows' KV
        writes into the garbage block, and the program returns the
        greedy token at EVERY row — the host's exact accept oracle. Row
        0's math is the decode program's term for term, so a verify
        step that accepts nothing still emits the identical token the
        plain decode step would have."""
        jnp = self._jnp
        dmodule, dequant = self._dmodule, self.engine._dequantize
        logits_of = self.engine._logits_of

        def fn(qparams, cache, tokens, tables, lengths, num_valid, rng):
            params = dequant(qparams)
            paging = self._paging(tokens, tables, lengths, num_valid)
            out, vars_ = dmodule.apply({"params": params, "cache": cache},
                                       tokens, mutable=["cache"],
                                       paging=paging)
            logits = logits_of(out)                       # [N, k+1, V]
            n, t, v = logits.shape
            toks = self._sample(logits.reshape(n * t, v), rng)
            return toks.reshape(n, t), vars_["cache"]

        return self._jit(
            fn, "serving_verify",
            f"serving.verify[slots={self.config.decode_slots},"
            f"k={self.spec_k}]")

    def _build_cow(self):
        """Copy one pool block's rows onto another across every cache
        leaf (key/value pools and, under int8 KV, their scale side
        pools), all layers at once — the device half of partial-tail
        copy-on-write. Every leaf is ``[layers, blocks, block_size,
        lanes]``; ``POOL_BLOCK_AXIS`` names the block axis."""
        jax = self._jax
        from deepspeed_tpu.ops.decode_attention import POOL_BLOCK_AXIS

        def fn(cache, src, dst):
            def copy(p):
                row = jax.lax.dynamic_index_in_dim(
                    p, src, axis=POOL_BLOCK_AXIS, keepdims=False)
                return jax.lax.dynamic_update_index_in_dim(
                    p, row, dst, POOL_BLOCK_AXIS)

            return jax.tree_util.tree_map(copy, cache)

        return self._jit(fn, "serving_cow", "serving.cow", donate=0)

    def _build_migrate(self, B: int):
        """Scatter ``B`` migrated pool blocks (every cache leaf — K/V
        pools and, under int8 KV, their scale side pools ride the same
        block indices; all layers of a block travel together) onto this
        replica's pool at the freshly allocated destination blocks. The
        import half of live KV migration: rows land on exactly the
        ``(layer, block, offset)`` every later paged write and the paged
        kernel address through the rewritten block table, so the resumed
        decode is bit-identical to never having moved. ``rows`` leaves
        are ``[layers, B, block_size, lanes]``, the pool's own shape
        with ``B`` on ``POOL_BLOCK_AXIS``."""
        jax = self._jax

        def fn(cache, rows, dst):
            return jax.tree_util.tree_map(
                lambda p, r: p.at[:, dst].set(r), cache, rows)

        return self._jit(fn, f"serving_migrate_B{B}",
                         f"serving.migrate[blocks={B}]", donate=0)

    def _next_rng(self):
        self._rng, sub = self._jax.random.split(self._rng)
        return sub

    def _req_samp_args(self, req: Request):
        """The keyed prefill/chunk programs' per-request sampling row
        ([1]-shaped, matching their batch of one). Greedy requests ride
        with flag 0 — the argmax leg, bit-identical to the rng path."""
        jnp = self._jnp
        on = 1 if req.do_sample else 0
        return (jnp.asarray([req.seed or 0], jnp.uint32),
                jnp.asarray([on], jnp.int32),
                jnp.asarray([req.temperature
                             if req.temperature is not None else 1.0],
                            jnp.float32),
                jnp.asarray([req.top_k or 0], jnp.int32),
                jnp.asarray([req.top_p or 0.0], jnp.float32))

    def _slot_samp_args(self, live):
        """The keyed decode program's per-slot sampling arrays (greedy
        slots carry flag 0, and rows outside ``live`` an idle slot's
        row)."""
        jnp = self._jnp
        rows = (self._seeds, self._samp_on, self._temps, self._top_ks,
                self._top_ps)
        return tuple(jnp.asarray(np.where(live, a, a.dtype.type(idle)))
                     for a, idle in zip(rows, _IDLE_SAMP))

    # ------------------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 0, **kwargs) -> Request:
        """Admit one request (non-blocking). Returns the Request; its
        ``state`` is ``queued`` on success or ``shed`` (with
        ``finish_reason``) when admission control rejected it."""
        prompt = [int(t) for t in np.asarray(prompt).ravel()]
        req = Request(prompt=prompt, max_new_tokens=int(max_new_tokens),
                      **kwargs)
        if req.keep_selected and not getattr(
                type(self.engine.module), "serve_selected", False):
            raise ValueError(
                f"keep_selected: {type(self.engine.module).__name__}'s "
                "serving programs return no selected keys")
        if self.sched.submit(req):
            self.resilience.serving_request_begin()
            self.telemetry.emit("serving", "request.queued",
                                step=self._step_count,
                                request_id=req.request_id,
                                prompt_len=req.prompt_len)
        else:
            self._record(req, shed=True, began=False)
        return req

    # ------------------------------------------------------------------
    def step(self) -> List[Request]:
        """One scheduler iteration: abandon blown deadlines, splice queued
        requests into free slots, advance mid-prefill prompts one budgeted
        chunk, then advance every decode-ready sequence one token. Returns
        requests finished this step."""
        # (what a flush between two calls finished is reported here too)
        done, self._late = self._late, []
        led = self._ledger
        before = (led["schedule"], led["prefill"], led["decode"],
                  led["emit"], led["dispatch"], led["sync"])
        with self._bracket("step", ledger="step", step=self._step_count + 1,
                           busy=self._busy,
                           queue_depth=len(self.sched.queue)) as whole:
            with self._bracket("schedule", span="schedule",
                               ledger="schedule") as ph:
                now = ph.t0
                # deadline sweep over running work
                for slot, req in self.sched.running():
                    if self.sched.expired(req, now):
                        self._finish(req, "deadline", now, done)
                # splice admissions into free slots (no recompilation:
                # bucket set)
                admitted, shed = self.sched.admit(now)
                for req in shed:
                    self._record(req, shed=True, began=True)
            for slot, req, table in admitted:
                self._begin(slot, req, self._slot_table(slot, table), done)
            self._prefill_chunks(done)
            # one decode step for the whole slot batch (mid-prefill slots
            # are idle decode rows: garbage table, outputs ignored); with
            # speculation on, the verify program IS the decode step
            if self._decode_ready():
                if self._proposer is not None:
                    self._spec_step(done)
                else:
                    self._decode_step(done)
            if self._flight is not None and not any(
                    self.sched.slots[slot] is req
                    for slot, req in self._flight.pairs):
                # the step in flight has no taker left (eos, a cancel,
                # the deadline sweep): fetched now, its rows dropped
                self._decode_step(done, ahead=False)
        self._note_step(whole.t0, whole.t1, before)
        if self._step_trace.enabled:
            g = self.sched.gauges()
            self._step_trace.flush(self._step_count,
                                   busy=g.get("slots_busy"),
                                   queue_depth=g.get("queue_depth"))
        return done

    def _note_step(self, t0: float, t1: float, before: tuple):
        """Keep the step among the slowest of the stats window if it is
        one, from the two clock reads its ``step`` bracket made: its wall
        time, and the SEAM before it (the last step's end to this one's
        start, less what the gateway's pump spent idle for want of work;
        none after a step that left the engine with nothing to do).
        A few subtractions a step; the row, with the phases' seconds as
        differences of the ledger, is built only for a step that enters
        the kept ones. One warning line for a step over
        ``SLOW_STEP_LOG_MS``: an untraced run that stalls says in its own
        log which step stood still, and in which phase."""
        proc = process_ledger.LEDGER
        idle, gc_secs = proc.seconds["pump_idle"], proc.gc["pause_secs"]
        # (a step that leaves nothing in flight, prefilling or queued
        # leaves an engine idle for want of work: no seam after it)
        last, self._last_end = self._last_end, (
            t1, idle, gc_secs, proc.first_calls,
            bool(self._flight is not None or self._prefilling
                 or self.sched.queue))
        self._step_calls += 1
        wall = t1 - t0
        seam = max(t0 - last[0] - (idle - last[1]), 0.0) if last[4] else 0.0
        slow = self._slow
        if len(slow) == SLOW_STEPS_KEPT and wall + seam <= slow[0][0]:
            return
        led = self._ledger
        # (a step that held a program's first call compiled or loaded it)
        first_call = proc.first_calls != last[3]
        row = {"at_s": round(t0 - proc.started_at, 6),
               "step": self._step_calls,
               "wall_ms": round(1e3 * wall, 3),
               "seam_ms": round(1e3 * seam, 3),
               **{f"{name}_ms": round(1e3 * (led[name] - was), 3)
                  for name, was in zip(_STEP_PARTS, before)},
               "gc_ms": round(1e3 * (gc_secs - last[2]), 3),
               "busy": self._busy, "queue_depth": len(self.sched.queue),
               "first_call": first_call}
        entry = (wall + seam, self._step_calls, row)
        if len(slow) < SLOW_STEPS_KEPT:
            heapq.heappush(slow, entry)
        else:
            heapq.heapreplace(slow, entry)
        if 1e3 * (wall + seam) > SLOW_STEP_LOG_MS and not first_call:
            logger.warning(
                f"serving step {row['step']} took {row['wall_ms']:.0f} ms "
                f"after a seam of {row['seam_ms']:.0f} ms: schedule "
                f"{row['schedule_ms']:.0f}, prefill {row['prefill_ms']:.0f}, "
                f"decode {row['decode_ms']:.0f}, emit {row['emit_ms']:.0f}, "
                f"gc {row['gc_ms']:.0f}; of prefill and decode, dispatch "
                f"{row['dispatch_ms']:.0f} and sync {row['sync_ms']:.0f} "
                f"(busy {row['busy']}, queued {row['queue_depth']})")

    def _begin(self, slot: int, req: Request, table: np.ndarray,
               done: List[Request]):
        """Route a fresh admission: legacy whole-prompt bucketed prefill
        (the zero-feature path, program-identical to PR 4), or the
        chunked/prefix-continued path when the request has pooled prefix
        tokens to skip or chunking is on."""
        if req.cow is not None:
            # partial-tail copy-on-write: the matched block will be
            # appended to, so the request's own fresh block receives a
            # device copy of its rows before anything else runs; the
            # source unpins once the copy is in flight
            with self._bracket("cow", span="cow", trace=req.trace,
                               src=req.cow[0], dst=req.cow[1]):
                self._cow_copy(*req.cow)
            self.block_mgr.cow_done(req.request_id)
        if not self.chunk_tokens and req.cached_len == 0:
            self._prefill(slot, req, table, done)
            return
        self._prefilling[slot] = req
        self._pf_tables[slot] = table
        self._pf_pos[slot] = req.cached_len
        req.length = req.cached_len

    def _prefill(self, slot: int, req: Request, table: np.ndarray,
                 done: List[Request]):
        jnp = self._jnp
        T = bucket_for(req.prompt_len, self.buckets)
        if T not in self._prefill_fns:
            self._first_call(f"serving_prefill_T{T}")
            self._prefill_fns[T] = self._build_prefill(T)
        with self._bracket("prefill", span="prefill", trace=req.trace,
                           ledger="prefill", bucket=T,
                           prompt_len=req.prompt_len,
                           request_id=req.request_id) as ph:
            with self._bracket("prefill.dispatch", ledger="dispatch"):
                ids = np.zeros((1, T), np.int32)
                ids[0, :req.prompt_len] = req.prompt
                tail = (self._req_samp_args(req) if self._keyed
                        else (self._next_rng(),))
                tok, self.cache, *selected = self._prefill_fns[T](
                    self.engine.params, self.cache, jnp.asarray(ids),
                    jnp.asarray(table[None]),
                    jnp.asarray([req.prompt_len], jnp.int32), *tail)
            with self._bracket("prefill.sync", ledger="sync"):
                tok = np.asarray(tok)
        if self._first_open:
            self._first_result()
        self._count("prefill", tok[1:])
        self._keep_routed(req, self._routed(tok, 1), req.prompt_len)
        self._keep_selected(req, selected, 0, req.prompt_len)
        tok = int(tok[0])
        self._prefill_done(req, ph)
        req.prefill_chunks = 1
        self._slot_live(slot, req, table, tok, done)

    @staticmethod
    def _keep_routed(req: Request, routed, tokens: int):
        if routed is not None:
            req.routed.append(routed[0, :tokens])

    def _prefill_done(self, req: Request, ph):
        """One closed prefill bracket: the request's own share of the
        ledger's ``prefill`` seconds, and the call count."""
        req.prefill_secs += ph.t1 - ph.t0
        self._ledger["prefill_calls"] += 1

    # ------------------------------------------------------------------
    def _prefill_chunks(self, done: List[Request]):
        """Advance mid-prefill prompts. With chunking on, at most
        ``prefill_chunk_tokens`` prompt tokens are processed per step
        (round-robin over slots, so a long prompt never starves a later
        short one — the TTFT bound); with chunking off (prefix-cache
        tails) each pending tail completes now in one bucketed chunk."""
        if not self._prefilling:
            return
        budget = self.chunk_tokens or None
        spent = 0
        slots = sorted(self._prefilling)
        start = next((i for i, s in enumerate(slots)
                      if s >= self._pf_next), 0)
        for slot in slots[start:] + slots[:start]:
            req = self._prefilling.get(slot)
            if req is None:
                continue
            table = self._pf_tables[slot]
            pos = self._pf_pos[slot]
            remaining = req.prompt_len - pos
            step_len = (min(self.chunk_tokens, remaining)
                        if self.chunk_tokens else remaining)
            T = self.chunk_tokens or bucket_for(remaining, self.buckets)
            tok = self._chunk_call(req, table, pos, step_len, T)
            self._pf_pos[slot] = pos + step_len
            req.length = pos + step_len
            req.prefill_chunks += 1
            if pos + step_len >= req.prompt_len:
                del self._prefilling[slot]
                self._pf_tables.pop(slot, None)
                self._pf_pos.pop(slot, None)
                self._slot_live(slot, req, table, tok, done)
            if budget is not None:
                spent += step_len
                if spent >= budget:
                    self._pf_next = slot + 1
                    return

    def _chunk_call(self, req: Request, table: np.ndarray, pos: int,
                    step_len: int, T: int) -> int:
        jnp = self._jnp
        if T not in self._chunk_fns:
            self._first_call(f"serving_chunk_T{T}")
            self._chunk_fns[T] = self._build_chunk(T)
        with self._bracket("prefill", span="prefill_chunk", trace=req.trace,
                           ledger="prefill", pos=pos, tokens=step_len,
                           bucket=T, request_id=req.request_id) as ph:
            with self._bracket("prefill.dispatch", ledger="dispatch"):
                ids = np.zeros((1, T), np.int32)
                ids[0, :step_len] = req.prompt[pos:pos + step_len]
                tail = (self._req_samp_args(req) if self._keyed
                        else (self._next_rng(),))
                tok, self.cache, *selected = self._chunk_fns[T](
                    self.engine.params, self.cache, jnp.asarray(ids),
                    jnp.asarray(table[None]), jnp.asarray([pos], jnp.int32),
                    jnp.asarray([step_len], jnp.int32), *tail)
            with self._bracket("prefill.sync", ledger="sync"):
                tok = np.asarray(tok)
        if self._first_open:
            self._first_result()
        self._count("prefill", tok[1:])
        self._keep_routed(req, self._routed(tok, 1), step_len)
        self._keep_selected(req, selected, 0, step_len)
        self._prefill_done(req, ph)
        return int(tok[0])

    def _slot_live(self, slot: int, req: Request, table: np.ndarray,
                   tok: int, done: List[Request]):
        """Prompt fully pooled: index the prompt for future prefix hits,
        join the decode batch, and emit the first sampled token."""
        with self._bracket("emit", span="emit", ledger="emit") as ph:
            self._mark_live(req, ph.t0)
            req.length = req.prompt_len
            self._tables[slot] = table
            self._lengths[slot] = req.prompt_len
            self._last_tokens[slot] = tok
            if self._keyed:
                self._set_samp_slot(slot, req)
            if self.prefix is not None:
                # BEFORE any finish: insertion must precede release so a
                # one-token request's blocks park evictable, not freed
                self.prefix.insert(req.prompt, table)
            finished = (tok == req.eos_token_id
                        or len(req.tokens) + 1 >= req.max_new_tokens)
            req.emit_token(tok, finished)
            if finished:
                reason = "eos" if tok == req.eos_token_id else "max_tokens"
                self._finish(req, reason, self.clock(), done)

    def _ledger_mark(self):
        """The five numbers a request's record is made from, O(1):
        seconds in prefill and decode brackets, decode steps and busy-slot
        steps, and the process's seconds inside the garbage collector, all
        cumulative."""
        led = self._ledger
        return (led["prefill"], led["decode"], self._step_count,
                led["busy_slot_steps"],
                process_ledger.LEDGER.gc["pause_secs"])

    def _mark_live(self, req: Request, now: float):
        """The request joins the decode batch at ``now``: its first
        token's timestamp and the ledger as it stood then. A step in
        flight is counted at its fetch, inside this request's decode
        life, and is none of its steps: the mark counts it already."""
        req.first_token_ts = now
        prefill, decode, steps, busy, gc_secs = self._ledger_mark()
        if self._flight is not None:
            steps, busy = steps + 1, busy + len(self._flight.pairs)
        req.live_mark = (prefill, decode, steps, busy, gc_secs)

    def _set_samp_slot(self, slot: int, req: Request):
        """Load one slot's sampling row from the request's (resolved)
        knobs — the ONLY per-slot sampler state; the key itself folds
        from (seed, position) inside the program every step."""
        self._seeds[slot] = int(req.seed or 0) & 0xFFFFFFFF
        self._samp_on[slot] = 1 if req.do_sample else 0
        self._temps[slot] = (req.temperature
                             if req.temperature is not None else 1.0)
        self._top_ks[slot] = req.top_k or 0
        self._top_ps[slot] = req.top_p or 0.0

    def _clear_samp_slot(self, slot: int):
        (self._seeds[slot], self._samp_on[slot], self._temps[slot],
         self._top_ks[slot], self._top_ps[slot]) = _IDLE_SAMP

    def _cow_copy(self, src: int, dst: int):
        jnp = self._jnp
        if self._cow_fn is None:
            self._cow_fn = self._build_cow()
        self.cache = self._cow_fn(self.cache, jnp.asarray(src, jnp.int32),
                                  jnp.asarray(dst, jnp.int32))

    def _decode_step(self, done: List[Request], ahead: bool = True):
        """One decode step fetched and emitted; and, with ``ahead``, the
        NEXT one dispatched first, so that the device computes step N+1
        while the host fetches step N, emits it, and goes round its loop
        (the handlers' writes, the next schedule pass and dispatch). What
        step N+1 needs of step N is on the device already (the sampled
        tokens) or known without looking (the lengths, + 1); its tables
        and sampling rows never change while a request decodes. At most
        one step is in flight on return (``step()`` then fetches one
        that no running sequence needs any more). ``ahead=False`` only
        fetches the step in flight: the flush (``_flush``)."""
        if self._feed_fn is None:
            self._first_feed()
        if self._decode_fn is None:
            self._first_call("serving_decode")
            self._decode_fn = self._build_decode()
        ready = self._decode_ready()
        with self._bracket("decode", span="decode_step", ledger="decode",
                           active=len(ready)) as ph:
            if ahead:
                with self._bracket("decode.dispatch", ledger="dispatch"):
                    # (the call that finds nothing in flight dispatches
                    # the step it will fetch, then the one ahead of it)
                    flight = (self._flight if self._flight is not None
                              else self._dispatch(None, ready))
                    self._flight = self._dispatch(flight, ready)
            else:
                flight, self._flight = self._flight, None
            with self._bracket("decode.sync", ledger="sync"):
                # the ONE designed host sync per decode step: sampled
                # tokens must reach the host to stream to callers and
                # drive finish logic
                toks = np.asarray(flight.toks)  # graft-lint: disable=GL04
        if self._first_open:
            self._first_result()
        now = ph.t1
        # counted here, at the fetch, from the fetched step's own view
        self._count("decode", toks[len(self._lengths):])
        routed = self._routed(toks, len(self._lengths))
        self._step_boundary(len(flight.pairs), flight.lengths, 1)
        with self._bracket("emit", span="emit", ledger="emit"):
            for slot, req in flight.pairs:
                if self.sched.slots[slot] is not req:
                    # the row's request left its slot after the step was
                    # dispatched (eos at the step before, a cancel, a
                    # blown deadline; the slot may have a new tenant):
                    # nothing of the row is delivered. It wrote one KV
                    # row past the request's last token, into a block
                    # (or the slot's ring) the request had been given:
                    # harmless, because every program that could reuse
                    # the block is dispatched after this one on the same
                    # device queue, and the prefix cache indexes only
                    # whole prompt tokens (test_serving_lookahead.py).
                    self._ledger["decode_dropped_rows"] += 1
                    continue
                if routed is not None:
                    req.routed.append(routed[slot])
                self._keep_selected(req, flight.selected, slot, 1)
                tok = int(toks[slot])
                req.length += 1
                self._lengths[slot] = req.length
                self._last_tokens[slot] = tok
                finished = (tok == req.eos_token_id
                            or len(req.tokens) + 1 >= req.max_new_tokens
                            or req.length + 1 > self.max_len)
                req.emit_token(tok, finished)
                if finished:
                    reason = ("eos" if tok == req.eos_token_id else
                              "max_tokens" if len(req.tokens)
                              >= req.max_new_tokens else "window")
                    self._finish(req, reason, now, done)

    def _decode_ready(self):
        return [(s, r) for s, r in self.sched.running()
                if s not in self._prefilling]

    def _dispatch(self, behind: Optional[_Flight],
                  ready) -> Optional[_Flight]:
        """Put one decode step on the device's queue for the decode-ready
        ``ready``, behind the step in flight (``behind``, or None): the
        *dispatched* view, one step
        ahead of the fetched one (``_lengths``, ``_last_tokens``). A row
        of ``behind`` goes on with its token left on the device and its
        length + 1, unless ``behind`` is its request's last step by the
        count of tokens or by the context limit (known without the
        fetch: then it is an idle row here, as a finished slot's is).
        Every other decode-ready slot went live since ``behind`` was
        dispatched and brings the host's row. None, and nothing
        dispatched, where no row is left."""
        jnp = self._jnp
        onward = np.zeros(len(self._lengths), bool)
        live = np.zeros(len(self._lengths), bool)
        was = dict(behind.pairs) if behind is not None else {}
        pairs = []
        for slot, req in ready:
            if was.get(slot) is req:
                if (len(req.tokens) + 1 >= req.max_new_tokens
                        or req.length + 2 > self.max_len):
                    continue
                onward[slot] = True
            live[slot] = True
            pairs.append((slot, req))
        if not pairs:
            return None
        lengths = np.where(live, self._lengths + onward, 0).astype(np.int32)
        fresh = np.where(onward, -1, np.where(live, self._last_tokens, 0))
        tail = (self._slot_samp_args(live) if self._keyed
                else (self._next_rng(),))
        toks, self.cache, *selected = self._decode_fn(
            self.engine.params, self.cache,
            self._feed_fn(self._prev_toks, fresh.astype(np.int32)),
            jnp.asarray(np.where(live[:, None], self._tables, 0)),
            jnp.asarray(lengths), *tail)
        self._prev_toks = toks
        if behind is not None:
            self._ledger["decode_ahead_steps"] += 1
        return _Flight(toks, pairs, lengths, selected)

    def _flush(self) -> List[Request]:
        """Fetch and deliver the step in flight, if there is one: what
        reads or moves a sequence's state from outside the step loop
        does this first. Returns the requests it finished."""
        done: List[Request] = []
        if self._flight is not None:
            self._decode_step(done, ahead=False)
        return done

    def _kv_write_form(self, tq: int, active: int) -> str:
        """Who wrote the KV rows of a step of ``tq`` tokens a slot with
        ``active`` busy rows: ``"kernel"`` where the model says its paged
        kernel call writes so many itself (``kv_write_most``: GPT-2's
        decode program, for the rows of its work list only), else
        ``"scatter"`` (XLA scatters of every slot's row)."""
        if tq not in self._kv_most:
            most = getattr(self._dmodule, "kv_write_most", None)
            self._kv_most[tq] = (None if most is None else
                                 most(self.config.decode_slots, tq))
        most = self._kv_most[tq]
        return "kernel" if most is not None and active <= most else "scatter"

    def _step_boundary(self, active: int, lengths: np.ndarray, tq: int):
        """One decode (or verify) step of ``tq`` tokens a slot is over,
        dispatched with ``lengths`` for ``active`` live rows: the
        ledger's counts, the telemetry step boundary, and, under
        telemetry only, the load gauges (the slot scan is not paid with
        telemetry off)."""
        self._step_count += 1
        self._kv_write[self._kv_write_form(tq, active)] += 1
        self._busy = active
        self._ledger["busy_slot_steps"] += active
        if self._kv_kinds:
            # what the step just run had to read, by the model's own
            # arithmetic on its busy rows' lengths: a layer that pages its
            # rows every token of a sequence, a window layer what its ring
            # holds, a convolution's state its fixed size a busy slot
            live = self._live_bytes(lengths[lengths > 0].astype(np.int64))
            for kind, nbytes in live.items():
                self._ledger[f"kv_live_bytes.{kind}"] += int(nbytes)
        self.telemetry.on_step_boundary(self._step_count, samples=active)
        # per-step load gauges on the event stream: the router's health
        # signals come from here, not from private scheduler state
        if self.telemetry.enabled:
            g = self.gauges()
            self.telemetry.emit("serving", "step.gauges",
                                step=self._step_count, **g)
            self._metrics_step_gauges(g)
        # host-observed per-step token progress: a server saturated with
        # long generations must not be judged hung between completions
        self.resilience.serving_step_progress()

    def _spec_step(self, done: List[Request]):
        """One speculative decode step: propose draft tokens on the host
        (``draft`` span), score every slot's pending token + proposals
        in ONE compiled verify dispatch, then commit the longest prefix
        the greedy oracle agreed with (``verify``/``spec_commit``
        spans). Emits 1 to ``k + 1`` tokens per active slot for the
        dispatch cost of one decode step; proposals right-pad to ``k``
        against the garbage block so the program shape never changes."""
        jnp = self._jnp
        # synchronous: the proposer needs the tokens on the host. The
        # plain path never runs beside it, so nothing is ever in flight
        assert self._flight is None
        if self._verify_fn is None:
            self._first_call("serving_verify")
            self._verify_fn = self._build_verify()
        k = self.spec_k
        active = self._decode_ready()
        tokens = np.zeros((self.config.decode_slots, k + 1), np.int32)
        tokens[:, 0] = self._last_tokens
        num_valid = np.ones((self.config.decode_slots,), np.int32)
        proposals: Dict[int, List[int]] = {}
        for slot, req in active:
            budget = self.sched.speculative_budget(req, k)
            props: List[int] = []
            if budget > 0:
                with self._bracket("draft", span="draft", trace=req.trace,
                                   proposer=self._proposer.name,
                                   budget=budget):
                    props = [int(t) for t in
                             self._proposer.propose(req, budget)][:budget]
            proposals[slot] = props
            if props:
                tokens[slot, 1:1 + len(props)] = props
                num_valid[slot] = 1 + len(props)
            # open the speculative ledger window over the verify write
            # extent [0, length + 1 + n_p). Admission's worst-case
            # reservation covers every window THIS engine can open, so
            # the grant must be empty — a future lazy-allocation policy
            # that takes real grants must also extend the slot's row of
            # self._tables first, or the verify writes would scatter
            # into the garbage block (the ledger stays general; the
            # fuzz drives its granting paths directly)
            granted = self.block_mgr.speculate(req.request_id,
                                               req.length + 1 + len(props))
            assert not granted, \
                "speculative grant without a device table update"
        with self._bracket("decode", span="decode_step", ledger="decode",
                           active=len(active)) as ph:
            with self._bracket("decode.dispatch", ledger="dispatch"):
                toks, self.cache = self._verify_fn(
                    self.engine.params, self.cache, jnp.asarray(tokens),
                    jnp.asarray(self._tables), jnp.asarray(self._lengths),
                    jnp.asarray(num_valid), self._next_rng())
            with self._bracket("decode.sync", ledger="sync"):
                # the ONE designed host sync per decode step (same
                # contract as the non-speculative loop): verified tokens
                # drive commit/finish
                toks = np.asarray(toks)  # graft-lint: disable=GL04
        if self._first_open:
            self._first_result()
        t0, now = ph.t0, ph.t1
        # chaos seam: a replica killed BETWEEN verify and commit has
        # emitted nothing from this window — host state is exactly the
        # pre-step state, so a retry or failover replays cleanly and
        # the router's exactly-once splice sees no speculative token
        raise_if("serving.spec_commit")
        self._spec_steps += 1
        self._step_boundary(len(active), self._lengths, k + 1)
        with self._bracket("emit", span="emit", ledger="emit"):
            self._spec_emit(active, proposals, toks, t0, now, done)

    def _spec_emit(self, active, proposals, toks, t0: float, now: float,
                   done: List[Request]):
        """Accept, commit and stream every active slot's verified row."""
        for slot, req in active:
            props = proposals[slot]
            accepted = 0
            for p in props:
                if int(toks[slot, accepted]) == p:
                    accepted += 1
                else:
                    break
            # draft AND accepted counters land here, past the chaos
            # seam: a step killed between verify and commit counted
            # nothing, so its retry cannot double-count the window
            req.draft_tokens += len(props)
            self._window_draft_tokens += len(props)
            req.accepted_tokens += accepted
            self._window_accepted_tokens += accepted
            if self._tracer.enabled and req.trace is not None:
                # per-request view of the SHARED batched verify dispatch
                self._tracer.record_span(
                    "verify", req.trace["trace"], to_ns(t0), to_ns(now),
                    parent=req.trace.get("serve_id"),
                    proposed=len(props), accepted=accepted,
                    request_id=req.request_id)
            with self._bracket("spec_commit", span="spec_commit",
                               trace=req.trace, accepted=accepted):
                finished, reason = self._spec_commit(slot, req, toks[slot],
                                                     accepted)
            if finished:
                self._finish(req, reason, now, done)

    def _spec_commit(self, slot: int, req: Request, row, accepted: int):
        """Commit one verified row: emit the model's greedy tokens at
        rows ``0..accepted`` (the accepted drafts, then the correction —
        or, with everything accepted, the free bonus token) under the
        sequential finish semantics, so eos / token budget / model
        window stop the stream exactly where non-speculative decode
        would. The accepted extent folds into the block ledger in place
        (its KV was written by the verify dispatch); the rejected tail
        drops without copies — its rows sit past the committed length,
        masked out of every later attention window and overwritten by
        the next step's writes."""
        finished, reason = False, None
        for i in range(accepted + 1):
            tok = int(row[i])
            req.length += 1
            self._lengths[slot] = req.length
            self._last_tokens[slot] = tok
            finished = (tok == req.eos_token_id
                        or len(req.tokens) + 1 >= req.max_new_tokens
                        or req.length + 1 > self.max_len)
            req.emit_token(tok, finished)
            if finished:
                reason = ("eos" if tok == req.eos_token_id else
                          "max_tokens" if len(req.tokens)
                          >= req.max_new_tokens else "window")
                break
        # + 1: the pending last token's next write lands at req.length
        self.block_mgr.commit_speculative(req.request_id, req.length + 1)
        return finished, reason

    def _finish(self, req: Request, reason: str, now: float,
                done: List[Request]):
        if (self._tracer.enabled and req.trace is not None
                and req.first_token_ts):
            # one decode segment: first generated token -> finish (the
            # per-token cadence is the step loop's, not this request's)
            self._tracer.record_span(
                "decode", req.trace["trace"], to_ns(req.first_token_ts),
                to_ns(now), parent=req.trace.get("serve_id"),
                tokens=len(req.tokens), request_id=req.request_id)
        if req.live_mark is not None:
            req.finish_mark = self._ledger_mark()
        self.sched.finish(req, reason, now)
        # reset the slot's host-side row: an idle slot computes into the
        # garbage block until the next admission overwrites it
        if 0 <= req.slot < len(self._tables):
            self._tables[req.slot] = 0
            self._lengths[req.slot] = 0
            self._last_tokens[req.slot] = 0
            if self._keyed:
                self._clear_samp_slot(req.slot)
            self._prefilling.pop(req.slot, None)
            self._pf_tables.pop(req.slot, None)
            self._pf_pos.pop(req.slot, None)
        self._record(req, shed=False, began=True)
        done.append(req)
        self.finished.append(req)
        if req.routed:
            kept = self._routed_kept
            kept[req.request_id] = np.concatenate(req.routed)
            req.routed = []
            while len(kept) > self.config.routed_experts_kept:
                del kept[next(iter(kept))]
        if req.selected:
            kept = self._selected_kept
            kept[req.request_id] = np.concatenate(req.selected)
            req.selected = []
            while len(kept) > SELECTED_KEYS_KEPT:
                del kept[next(iter(kept))]

    def _record(self, req: Request, shed: bool, began: bool):
        rec = req.record()
        self.records.append(rec)
        self.telemetry.emit(
            "serving", "request.shed" if shed else "request.finish",
            step=self._step_count, **rec)
        self._metrics_record(req, rec, shed)
        if self._tracer.enabled and req.trace is not None:
            # close the replica-side root span (opened at admission);
            # queue-head sheds that never won a slot carry no handle
            end_span(req.trace.pop("serve", None),
                     end_ns=to_ns(req.finish_ts or req.submit_ts),
                     state=req.state, reason=req.finish_reason,
                     tokens=len(req.tokens))
        if not began:
            return  # never bracketed: submit-time shed
        if shed:
            self.resilience.serving_request_abandon()
        else:
            self._finished_count += 1
            self.resilience.serving_heartbeat(self._finished_count)

    def _metrics_record(self, req: Request, rec: dict, shed: bool):
        """Per-terminal-request registry feed: latency histograms,
        outcome/token counters, prefix-cache and spec-decode window
        gauges. One no-op instrument call per line when metrics are
        disarmed."""
        m = self._metrics
        m.counter("ds_serving_requests_total", ("outcome",)).labels(
            outcome="shed" if shed else "finished").inc()
        if rec.get("ttft_ms") is not None:
            m.histogram("ds_serving_ttft_ms").observe(rec["ttft_ms"])
        if rec.get("queue_ms") is not None:
            m.histogram("ds_serving_queue_ms").observe(rec["queue_ms"])
        if shed:
            return
        m.counter("ds_serving_tokens_total").inc(rec.get("new_tokens") or 0)
        # tokens prove a first token landed — a fake clock legitimately
        # reading 0.0 at that moment must not drop the observation (the
        # timestamp fields are 0.0-sentinel by dataclass convention)
        if req.tokens:
            m.histogram("ds_serving_decode_ms").observe(
                1e3 * max(req.finish_ts - req.first_token_ts, 0.0))
        if self.prefix is not None:
            self._window_prompt_tokens += rec.get("prompt_len") or 0
            self._window_hit_tokens += rec.get("prefix_hit_tokens") or 0
            if self._window_prompt_tokens:
                m.gauge("ds_prefix_cache_hit_rate").set(round(
                    self._window_hit_tokens
                    / self._window_prompt_tokens, 4))
        if self._proposer is not None:
            drafts = rec.get("draft_tokens") or 0
            acc = rec.get("accepted_tokens") or 0
            if drafts:
                m.counter("ds_spec_draft_tokens_total").inc(drafts)
                m.counter("ds_spec_accepted_tokens_total").inc(acc)
            if self._window_draft_tokens:
                m.gauge("ds_spec_acceptance_rate").set(round(
                    self._window_accepted_tokens
                    / self._window_draft_tokens, 4))

    def _metrics_step_gauges(self, g: dict):
        """Per-decode-step pool/queue gauges from the SAME ``gauges()``
        payload the ``step.gauges`` event carries (one slot scan, two
        consumers — the surfaces cannot disagree)."""
        m = self._metrics
        m.gauge("ds_serving_queue_depth").set(g.get("queue_depth", 0))
        m.gauge("ds_serving_slots_busy").set(g.get("slots_busy", 0))
        m.gauge("ds_serving_slots_total").set(g.get("slots_total", 0))
        bm = self.block_mgr
        usable = max(1, bm.num_blocks - 1)   # garbage block excluded
        used = bm.num_allocated
        tier = m.gauge("ds_kv_pool_blocks", ("tier",))
        tier.labels(tier="free").set(bm.num_free)
        tier.labels(tier="cached").set(bm.num_cached)
        tier.labels(tier="used").set(used)
        m.gauge("ds_kv_pool_occupancy").set(round(used / usable, 4))
        committed = int(g.get("committed_tokens", 0))
        capacity = used * self.config.block_size
        m.gauge("ds_kv_pool_fragmentation").set(
            round(1.0 - committed / capacity, 4) if capacity else 0.0)
        # the ledger, as counters: what it gained since the last publish
        now, was = dict(self._ledger), self._ledger_published
        self._ledger_published = now
        phase = m.counter("ds_serving_phase_seconds_total", ("phase",))
        for name in _PHASES:
            phase.labels(phase=name).inc(now[name] - was[name])
        m.counter("ds_serving_busy_slot_steps_total").inc(
            now["busy_slot_steps"] - was["busy_slot_steps"])
        m.counter("ds_serving_decode_ahead_steps_total").inc(
            now["decode_ahead_steps"] - was["decode_ahead_steps"])
        m.counter("ds_serving_decode_dropped_rows_total").inc(
            now["decode_dropped_rows"] - was["decode_dropped_rows"])
        # (the process's, not the engine's: published by whoever steps)
        gc_secs = process_ledger.LEDGER.gc["pause_secs"]
        m.counter("ds_host_gc_pause_seconds_total").inc(
            max(gc_secs - self._gc_published, 0.0))
        self._gc_published = gc_secs

    # ------------------------------------------------------------------
    def cancel(self, request_id: str, reason: str = "cancelled") -> bool:
        """Abandon one in-flight request (queued or mid-decode): its
        decode slot, KV blocks and token budget release immediately and
        it is recorded as shed with ``reason``. The multi-replica router
        calls this at failover so abandoned proxies never keep decoding
        on a replica that later recovers."""
        req = self.sched.cancel(request_id, reason, self.clock())
        if req is None:
            return False
        if 0 <= req.slot < len(self._tables):
            self._tables[req.slot] = 0
            self._lengths[req.slot] = 0
            self._last_tokens[req.slot] = 0
            if self._keyed:
                self._clear_samp_slot(req.slot)
            self._prefilling.pop(req.slot, None)
            self._pf_tables.pop(req.slot, None)
            self._pf_pos.pop(req.slot, None)
        self._record(req, shed=True, began=True)
        return True

    # ------------------------------------------------------------------
    # live KV-block migration seams (serving/migration.py orchestrates;
    # the router/fleet manager are the consumers — failover, drain and
    # fragmentation rebalance move committed state instead of replaying)
    def export_sequence(self, request_id: str) -> Optional[dict]:
        """Snapshot one decode-ready sequence's committed state for
        import on another replica: the request's identity and counters,
        its pending last token, and the per-block KV rows of every cache
        leaf (int8 side pools and their scales ride the same block
        indices), gathered on ``POOL_BLOCK_AXIS`` and split into
        per-TP-shard chunks along ``POOL_LANE_AXIS`` (``heads / tp``
        contiguous heads each) — the transfer unit the lane-sharded
        pools define. Read-only on the source (an open
        speculative window is dropped first — it is uncommitted by
        definition), so a transfer that dies downstream leaves this
        replica able to keep decoding or to serve a replay. Returns None
        when the request is not migratable (unknown, queued, or still
        mid-prefill — those replay/resubmit cheaply)."""
        raise_if("serving.migration.export", detail=request_id)
        self._no_migration_beside_slot_state("export_sequence")
        self._late.extend(self._flush())
        req = next((r for _, r in self.sched.running()
                    if r.request_id == request_id), None)
        if req is None or req.slot in self._prefilling or req.length <= 0:
            return None
        if self.block_mgr.speculating(request_id):
            self.block_mgr.drop_speculative(request_id)
        jax, jnp = self._jax, self._jnp
        from deepspeed_tpu.ops.decode_attention import (POOL_BLOCK_AXIS,
                                                        POOL_LANE_AXIS)

        bs = self.config.block_size
        covered = self.block_mgr.owned(request_id)[
            :blocks_for_tokens(req.length, bs)]
        tp = 1
        try:
            tp = int(dict(self.engine.mesh.shape).get("tp", 1))
        except Exception:
            tp = 1
        leaves, treedef = jax.tree_util.tree_flatten_with_path(self.cache)
        idx = jnp.asarray(np.asarray(covered, np.int32))
        heads = self._dmodule.config.n_head
        rows, wire_bytes = [], 0
        for path, leaf in leaves:
            r = np.asarray(jnp.take(leaf, idx, axis=POOL_BLOCK_AXIS))
            if path[-1].key.endswith("_scale"):
                # a scale row's padding lanes stay home
                r = r[..., :heads]
            if tp > 1 and heads % tp == 0:
                chunks = [np.ascontiguousarray(c)
                          for c in np.split(r, tp, axis=POOL_LANE_AXIS)]
            else:
                chunks = [r]
            wire_bytes += sum(c.nbytes for c in chunks)
            rows.append(chunks)
        return {
            "request_id": req.request_id,
            "prompt": list(req.prompt),
            "tokens": list(req.tokens),
            "max_new_tokens": int(req.max_new_tokens),
            "eos_token_id": int(req.eos_token_id),
            "deadline_ms": float(req.deadline_ms),
            "length": int(req.length),
            "last_token": int(self._last_tokens[req.slot]),
            "do_sample": bool(self.config.do_sample),
            # keyed per-request sampling state: the seed and knobs ARE
            # the whole sampler — position comes from length, so the
            # spliced slot resumes the stream bit-exactly with no
            # counter re-derivation (None for greedy requests)
            "sampling": ({
                "do_sample": True, "seed": int(req.seed or 0),
                "temperature": float(req.temperature
                                     if req.temperature is not None
                                     else 1.0),
                "top_k": int(req.top_k or 0),
                "top_p": float(req.top_p or 0.0),
            } if req.do_sample else None),
            "block_size": bs,
            "kv_cache_dtype": self.config.kv_cache_dtype or None,
            "tp_shards": tp,
            "blocks": len(covered),
            "rows": rows,
            "treedef": str(treedef),
            "wire_bytes": int(wire_bytes),
            "draft_tokens": int(req.draft_tokens),
            "accepted_tokens": int(req.accepted_tokens),
        }

    def import_sequence(self, export: Optional[dict],
                        deadline_ms: Optional[float] = None,
                        stream=None,
                        request_id: Optional[str] = None,
                        trace: Optional[dict] = None
                        ) -> Optional[Request]:
        """Splice an exported sequence into a free decode slot: allocate
        blocks, scatter the migrated rows onto this pool at exactly the
        ``(layer, block, offset)`` every later paged program addresses
        through the rewritten table, seed the request's token/sampling
        counters, and resume decoding mid-stream — NO prefill program
        dispatch. Returns None when the export cannot land here (pool
        geometry/dtype/sampling mismatch, no free slot, duplicate id, or
        not enough blocks) so the caller can fall back to replay. The
        block table commit happens last: a fault before it (the
        ``serving.migration.commit`` chaos seam) releases every block
        this call allocated and leaves the scheduler untouched."""
        if export is None:
            return None
        self._no_migration_beside_slot_state("import_sequence")
        self._late.extend(self._flush())
        rid = request_id or export["request_id"]
        samp = export.get("sampling")
        if (export["block_size"] != self.config.block_size
                or (export.get("kv_cache_dtype") or None)
                != (self.config.kv_cache_dtype or None)
                or bool(export["do_sample"]) != bool(self.config.do_sample)
                # a keyed sampled stream can only resume on a replica
                # whose decode program folds the same keys — a greedy
                # target would silently continue it greedily
                or (samp is not None and not self._keyed)
                or rid in self.sched._live_ids):
            return None
        slot = self.sched.free_slot()
        if slot is None:
            return None
        jax, jnp = self._jax, self._jnp
        from deepspeed_tpu.ops.decode_attention import POOL_LANE_AXIS

        mnt = int(export["max_new_tokens"]
                  or self.config.default_max_new_tokens)
        cost = len(export["prompt"]) + mnt
        if (cost > self.max_len or int(export["length"]) > cost
                or not self.block_mgr.can_allocate_shared(cost, (), None)):
            return None
        leaves, treedef = jax.tree_util.tree_flatten(self.cache)
        if str(treedef) != export["treedef"]:
            return None
        now = self.clock()
        req = Request(prompt=list(export["prompt"]),
                      max_new_tokens=mnt, request_id=rid,
                      eos_token_id=int(export["eos_token_id"]),
                      deadline_ms=(deadline_ms if deadline_ms is not None
                                   else export["deadline_ms"]),
                      stream=stream)
        if samp is not None:
            req.do_sample = True
            req.seed = int(samp["seed"])
            req.temperature = float(samp["temperature"])
            req.top_k = int(samp["top_k"])
            req.top_p = float(samp["top_p"])
        # delivered prefix rides along verbatim — seeded directly, NOT
        # via emit_token (the client already holds these tokens; the
        # stream fires only for tokens decoded after the splice)
        req.tokens = list(export["tokens"])
        req.draft_tokens = int(export.get("draft_tokens") or 0)
        req.accepted_tokens = int(export.get("accepted_tokens") or 0)
        req.submit_ts = now
        if req.tokens:
            # a spliced stream is live from the splice: its record's
            # decode life (and the ledger marks) start here
            self._mark_live(req, now)
        # router-stamped trace context: the spliced request's replica
        # spans join the CLIENT's trace under the migration attempt
        req.trace = dict(trace) if trace is not None else None
        table = self.block_mgr.allocate(rid, cost)
        try:
            B = int(export["blocks"])
            if B:
                rows_leaves = []
                for chunks, leaf in zip(export["rows"], leaves):
                    r = (chunks[0] if len(chunks) == 1 else np.concatenate(
                        chunks, axis=POOL_LANE_AXIS))
                    pad = [(0, 0)] * r.ndim
                    pad[POOL_LANE_AXIS] = (0, leaf.shape[POOL_LANE_AXIS]
                                           - r.shape[POOL_LANE_AXIS])
                    rows_leaves.append(jnp.asarray(np.pad(r, pad)))
                rows = jax.tree_util.tree_unflatten(treedef, rows_leaves)
                if B not in self._migrate_fns:
                    self._migrate_fns[B] = self._build_migrate(B)
                dst = jnp.asarray(np.asarray(table[:B], np.int32))
                self.cache = self._migrate_fns[B](self.cache, rows, dst)
            raise_if("serving.migration.commit", detail=rid)
            self.sched.splice(req, slot, now)
        except Exception:
            # rows already scattered are stale bytes in blocks the pool
            # no longer maps — harmless; the scheduler never saw us
            self.block_mgr.release(rid)
            raise
        req.length = int(export["length"])
        self._tables[slot] = table
        self._lengths[slot] = req.length
        self._last_tokens[slot] = int(export["last_token"])
        if self._keyed:
            self._set_samp_slot(slot, req)
        self.resilience.serving_request_begin()
        self.telemetry.emit("serving", "request.migrated_in",
                            step=self._step_count, request_id=rid,
                            blocks=int(export["blocks"]),
                            wire_bytes=int(export["wire_bytes"]),
                            length=req.length)
        return req

    def _no_migration_beside_slot_state(self, call: str):
        if self.slot_state or self.row_kind:
            raise NotImplementedError(self._beside_slot_state(
                f"live KV migration ({call})"))

    def migrate_out(self, request_id: str) -> bool:
        """Detach a migrated-away request from this replica: free its
        slot, blocks and token budget WITHOUT a shed record — the
        request is still live, on another replica, in the same client
        trace. Call only after the target committed its import."""
        self._late.extend(self._flush())
        now = self.clock()
        req = self.sched.migrate_out(request_id, now)
        if req is None:
            return False
        if 0 <= req.slot < len(self._tables):
            self._tables[req.slot] = 0
            self._lengths[req.slot] = 0
            self._last_tokens[req.slot] = 0
            if self._keyed:
                self._clear_samp_slot(req.slot)
            self._prefilling.pop(req.slot, None)
            self._pf_tables.pop(req.slot, None)
            self._pf_pos.pop(req.slot, None)
        if self._tracer.enabled and req.trace is not None:
            end_span(req.trace.pop("serve", None), end_ns=to_ns(now),
                     state="migrated", tokens=len(req.tokens))
        self.resilience.serving_request_abandon()
        self.telemetry.emit("serving", "request.migrated_out",
                            step=self._step_count, request_id=request_id,
                            tokens=len(req.tokens))
        return True

    def gauges(self) -> dict:
        """Instantaneous load gauges (queue depth, busy slots, free
        blocks): the payload of the per-step ``serving``/``step.gauges``
        telemetry event and the numbers the multi-replica router routes
        by — one public surface, no private-state reach-ins."""
        g = {**self.sched.gauges(), "free_blocks": self.block_mgr.num_free}
        if self.prefix is not None:
            g["cached_blocks"] = self.block_mgr.num_cached
        # decode-side fragmentation (same formula as the PR 14
        # ds_kv_pool_fragmentation gauge): the rebalance trigger
        committed = int(g.get("committed_tokens", 0))
        capacity = self.block_mgr.num_allocated * self.config.block_size
        g["kv_fragmentation"] = (round(1.0 - committed / capacity, 4)
                                 if capacity else 0.0)
        return g

    @property
    def pending(self) -> bool:
        return self.sched.pending

    def drain(self, max_steps: Optional[int] = None) -> List[Request]:
        """Step until queue and slots are empty (or ``max_steps``);
        returns every request finished during the drain."""
        out: List[Request] = []
        steps = 0
        while self.pending and (max_steps is None or steps < max_steps):
            out.extend(self.step())
            steps += 1
        # (nothing stays in flight behind a drain)
        out.extend(self._flush())
        return out

    def generate_batch(self, prompts, max_new_tokens: int = 0, **kwargs):
        """Convenience: submit every prompt, drain, return each request's
        generated tokens in submit order (None for shed requests)."""
        reqs = [self.submit(p, max_new_tokens=max_new_tokens, **kwargs)
                for p in prompts]
        self.drain()
        return [r.tokens if r.state == FINISHED else None for r in reqs]

    def reset_stats(self):
        """Clear the per-request records and scheduler counters (a bench
        epoch boundary between warmup and the measured window); in-flight
        requests and the cache pool are untouched."""
        self.records.clear()
        self.finished.clear()
        self._spec_steps = 0
        self._window_draft_tokens = 0
        self._window_accepted_tokens = 0
        self._window_prompt_tokens = 0
        self._window_hit_tokens = 0
        self._ledger_base = dict(self._ledger)
        self._slow = []
        self._gc_base = process_ledger.LEDGER.host_pauses()
        self.sched.reset_stats()

    def stats(self) -> dict:
        """Aggregate serving metrics (the bench's ``*_serving`` series)."""
        ttfts = [r["ttft_ms"] for r in self.records
                 if r.get("ttft_ms") is not None]
        rates = [r["tokens_per_sec"] for r in self.records
                 if r.get("tokens_per_sec") is not None]
        prefix_stats = None
        if self.prefix is not None:
            finished = [r for r in self.records if r["state"] != "shed"]
            prompt_toks = sum(r["prompt_len"] for r in finished)
            hit_toks = sum(r.get("prefix_hit_tokens", 0) for r in finished)
            prefix_stats = {
                **self.prefix.stats,
                "cached_blocks": self.block_mgr.num_cached,
                "evictions": self.block_mgr.evictions,
                "window_hit_rate": round(hit_toks / prompt_toks, 4)
                if prompt_toks else 0.0,
            }
        spec_stats = None
        if self._proposer is not None:
            # window counters, not the bounded records deque: a long
            # run past the deque's maxlen must not decay the ratios
            drafts = self._window_draft_tokens
            acc = self._window_accepted_tokens
            spec_stats = {
                "proposer": self._proposer.name,
                "num_speculative_tokens": self.spec_k,
                "draft_tokens": drafts,
                "accepted_tokens": acc,
                "acceptance_rate": round(acc / drafts, 4)
                if drafts else None,
                # aggregate extra tokens ONE verify dispatch bought,
                # over the stats window — the headline speculation win
                "accepted_tokens_per_step": round(acc / self._spec_steps, 4)
                if self._spec_steps else None,
            }
        from deepspeed_tpu.ops.attention import dispatch_counts

        s = self.sched.stats
        total = max(1, s["submitted"])
        led, base = self._ledger, self._ledger_base
        return {
            # the always-on ledger since the last reset_stats(): seconds
            # inside the schedule/prefill/decode/emit brackets, and the
            # counts taken at the same boundaries
            "phase_seconds": {k: led[k] - base[k] for k in _PHASES},
            "prefill_calls": led["prefill_calls"] - base["prefill_calls"],
            "busy_slot_steps": (led["busy_slot_steps"]
                                - base["busy_slot_steps"]),
            # how often the decode loop ran ahead: steps dispatched while
            # another was in flight, and rows fetched and not delivered
            "decode_ahead": {
                "steps": (led["decode_ahead_steps"]
                          - base["decode_ahead_steps"]),
                "dropped_rows": (led["decode_dropped_rows"]
                                 - base["decode_dropped_rows"])},
            # the model's own counters by kind of program, the live KV
            # bytes by kind of layer summed over decode steps (both empty
            # for a model that has neither), and which attention path
            # each call site took when its program was traced
            "model_counters": {
                phase: {name: led[f"{phase}.{name}"] - base[f"{phase}.{name}"]
                        for name in self._counter_names}
                for phase in ("prefill", "decode") if self._counter_names},
            "kv_live_bytes": {
                kind: led[f"kv_live_bytes.{kind}"]
                - base[f"kv_live_bytes.{kind}"]
                for kind in self._kv_kinds},
            "attention_paths": dispatch_counts(),
            # how many weights the engine laid out at start-up as its
            # decode program asked, and their bytes (zeros: not engaged)
            "weight_layouts": dict(self._weight_layouts),
            # the PROCESS's ledger (telemetry/process_ledger.py): where the
            # seconds from the process's start to ``ready`` went (never
            # reset), the collector's pauses over the stats window, and
            # the window's slowest steps by phase, slowest first
            "startup": process_ledger.LEDGER.snapshot(),
            "host_pauses": process_ledger.LEDGER.host_pauses(
                since=self._gc_base),
            "slow_steps": [row for _, _, row in sorted(self._slow,
                                                       reverse=True)],
            "prefix_cache": prefix_stats,
            "speculative": spec_stats,
            "finished": s["finished"], "shed": s["shed"],
            "shed_reasons": dict(s["shed_reasons"]),
            "shed_rate": round(s["shed"] / total, 4),
            "migrated_in": s["migrated_in"],
            "migrated_out": s["migrated_out"],
            "queue_peak": s["queue_peak"],
            "decode_steps": self._step_count,
            # ... of which by who wrote the step's KV rows: the kernel's
            # call for its busy rows, or XLA scatters of every slot's
            "kv_write": dict(self._kv_write),
            "ttft_ms_p50": round(float(np.percentile(ttfts, 50)), 3)
            if ttfts else None,
            "ttft_ms_p95": round(float(np.percentile(ttfts, 95)), 3)
            if ttfts else None,
            "tokens_per_sec_mean": round(float(np.mean(rates)), 2)
            if rates else None,
        }

    def destroy(self):
        """Drop compiled programs and the cache pool; destroys the wrapped
        engine only when this ServingEngine constructed it."""
        self._flush()
        self._prefill_fns.clear()
        self._chunk_fns.clear()
        self._decode_fn = self._feed_fn = self._prev_toks = None
        self._decode_asked = None
        self._cow_fn = None
        self._migrate_fns.clear()
        self._verify_fn = None
        self.cache = None
        if self._owns_engine:
            self.engine.destroy()
