"""One in-flight generation request.

Lifecycle: ``queued`` -> ``running`` (owns a decode slot + cache blocks)
-> ``finished`` (reason: ``eos`` | ``max_tokens`` | ``deadline``), or
``shed`` straight from submit/queue (reason: ``queue_full`` |
``inflight_tokens`` | ``too_long`` | ``deadline``). Timestamps are
host-monotonic; :meth:`Request.record` turns them into the telemetry
payload the serving event stream (and the gateway's SSE ``done`` event)
carries: TTFT, queue wait, tokens/s, and where the decode life went
(``prefill_ms``; ``decode_steps``, ``decode_ms``, ``blocked_ms``,
``host_ms``, ``batch_mean``, from two snapshots of the engine's ledger).
"""

import dataclasses
import itertools
from typing import Any, Callable, List, Optional

QUEUED = "queued"
RUNNING = "running"
FINISHED = "finished"
SHED = "shed"

_ids = itertools.count()


def _auto_id() -> str:
    return f"req-{next(_ids)}"


@dataclasses.dataclass
class Request:
    prompt: Any                       # 1-D int sequence (list/np array)
    max_new_tokens: int = 0           # 0 = serving default
    request_id: str = dataclasses.field(default_factory=_auto_id)
    eos_token_id: int = -1            # -1 disables early stop
    deadline_ms: float = 0.0          # 0 = serving default
    # stream(request, token, done) fires once per generated token, on the
    # scheduler thread, in generation order
    stream: Optional[Callable] = None
    # ---- keyed sampling (serving.sampling; engine resolves None knobs
    # to the block's defaults at admission and validates ranges) ----
    do_sample: bool = False
    # the request's reproducibility key: with do_sample on, token P is a
    # pure function of (seed, P, logits) — replayable state, carried
    # across failover/migration verbatim. None + do_sample = unseeded
    # legacy sampling, which a keyed engine sheds loudly.
    seed: Optional[int] = None
    temperature: Optional[float] = None
    top_k: Optional[int] = None
    top_p: Optional[float] = None

    # ---- runtime state (owned by the scheduler/engine) ----
    state: str = QUEUED
    tokens: List[int] = dataclasses.field(default_factory=list)
    finish_reason: Optional[str] = None
    submit_ts: float = 0.0
    admit_ts: float = 0.0             # left the queue, won a decode slot
    first_token_ts: float = 0.0
    finish_ts: float = 0.0
    slot: int = -1
    length: int = 0                   # tokens currently in the KV cache
    # ---- prefix-cache / chunked-prefill accounting (set at admit by
    # the scheduler, advanced by the engine's prefill path) ----
    cached_len: int = 0               # prompt tokens already in the pool
    prefix_hit_tokens: int = 0        # matched cached prefix length
    blocks_shared: int = 0            # physical blocks mapped read-only
    prefill_chunks: int = 0           # chunk-program calls this prefill
    # (src, dst) pool blocks: dst must receive a device copy of src's
    # rows before any append (partial-tail copy-on-write), or None
    cow: Optional[tuple] = None
    # ---- speculative-decoding accounting (advanced by the engine's
    # verify step; zero when speculation is off or never proposed) ----
    draft_tokens: int = 0             # proposer tokens sent to verify
    accepted_tokens: int = 0          # drafts the target model agreed with
    # ---- the engine's ledger at two moments (serving/engine.py): when
    # the request joined the decode batch and when it finished, each
    # (prefill secs, decode secs, decode steps, busy-slot steps, the
    # process's seconds inside the collector), all cumulative;
    # prefill_secs is the time inside its OWN prefill calls.
    # record() turns the pair into where its decode life went. ----
    prefill_secs: float = 0.0
    # under serving.routed_experts_kept: the chosen experts of each call
    # that processed tokens of this request, [tokens, layers x k] a call
    routed: list = dataclasses.field(default_factory=list)
    # where the request asks (a model whose programs return them): the
    # keys each processed query chose, [tokens, layers, words] a call
    keep_selected: bool = False
    selected: list = dataclasses.field(default_factory=list)
    live_mark: Optional[tuple] = None
    finish_mark: Optional[tuple] = None
    # ---- span-tracing context (telemetry/tracing.py) ----
    # {"trace": id, "parent": span id, ...}: set by the serving engine at
    # submit (tracing enabled), or stamped by the multi-replica router so
    # replica-side spans join the CLIENT's trace under the current
    # attempt span (a failover continues one trace, not two). None when
    # tracing is off — every consumer guards on it.
    trace: Optional[dict] = None

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def keyed(self) -> bool:
        """Replayable sampled request: every emitted position's token is
        regenerable bit-exactly from (seed, position) by any replica."""
        return self.do_sample and self.seed is not None

    @property
    def positions_emitted(self) -> int:
        """Generated positions already streamed — with ``length`` and the
        token list, the ONLY sampler state there is (counter-based keys
        have no hidden rng to carry across a migration or replay)."""
        return len(self.tokens)

    @property
    def done(self) -> bool:
        return self.state in (FINISHED, SHED)

    def emit_token(self, token: int, done: bool):
        self.tokens.append(int(token))
        if self.stream is not None:
            self.stream(self, int(token), done)

    def _decode_life(self) -> dict:
        """Where the time between the first token and the finish went,
        from the two ledger marks: inside decode programs
        (``decode_ms``), inside OTHER requests' prefills
        (``blocked_ms``), and the rest, the host loop (``host_ms``);
        the three add up to ``finish_ts - first_token_ts`` by
        construction. ``gc_ms`` is the process's time inside the garbage
        collector over the same life: a PART of the three (of whichever
        bracket the collection fell in), not a fourth term. All None for
        a request that never went live or never finished (shed,
        cancelled, migrated away)."""
        if self.live_mark is None or self.finish_mark is None:
            return dict.fromkeys(("prefill_ms", "decode_steps", "decode_ms",
                                  "blocked_ms", "host_ms", "batch_mean",
                                  "gc_ms"))
        prefill, decode, steps, busy, gc_secs = (
            b - a for a, b in zip(self.live_mark, self.finish_mark))
        life = max(self.finish_ts - self.first_token_ts, 0.0)
        return {
            "prefill_ms": round(1e3 * self.prefill_secs, 3),
            "decode_steps": steps,
            "decode_ms": round(1e3 * decode, 3),
            "blocked_ms": round(1e3 * prefill, 3),
            "host_ms": round(1e3 * (life - decode - prefill), 3),
            "batch_mean": round(busy / steps, 3) if steps else None,
            "gc_ms": round(1e3 * gc_secs, 3),
        }

    def record(self) -> dict:
        """JSON-safe per-request telemetry payload."""
        gen_secs = max(self.finish_ts - self.first_token_ts, 0.0)
        return {
            **self._decode_life(),
            "request_id": self.request_id,
            "state": self.state,
            "reason": self.finish_reason,
            "do_sample": bool(self.do_sample),
            "prompt_len": self.prompt_len,
            "new_tokens": len(self.tokens),
            "queue_ms": round(1e3 * max(
                self.admit_ts - self.submit_ts, 0.0), 3)
            if self.admit_ts else None,
            "ttft_ms": round(1e3 * (self.first_token_ts - self.submit_ts), 3)
            if self.first_token_ts else None,
            "tokens_per_sec": round(len(self.tokens) / gen_secs, 2)
            if len(self.tokens) > 1 and gen_secs > 0 else None,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "blocks_shared": self.blocks_shared,
            "prefill_chunks": self.prefill_chunks,
            "draft_tokens": self.draft_tokens,
            "accepted_tokens": self.accepted_tokens,
            "acceptance_rate": round(
                self.accepted_tokens / self.draft_tokens, 4)
            if self.draft_tokens else None,
        }
