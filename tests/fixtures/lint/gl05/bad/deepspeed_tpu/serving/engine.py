"""BAD: emits with kinds the registry has never heard of."""

from deepspeed_tpu.telemetry.events import make_event


class ServingEngine:
    def step(self):
        self.telemetry.emit("servign", "step.gauges", step=1)   # typo kind
        self._telemetry.emit("decode_stats", "tokens", step=1)  # new, never
        return make_event("bogus", "x", 0, 0, {})               # registered

    def trace(self):
        self.telemetry.emit("span", "prefil", step=1)        # typo name
        self._tracer.record_span("dequeue", "t1", 0, 1)      # unregistered
        with self._bracket("warmup", span="warmup"):         # unregistered
            pass
        self.telemetry.step_trace.mark("fwdbwd", 0, 1)       # unregistered

    def spec_step(self):
        # speculative-decoding near-misses: the registered names are
        # draft / verify / spec_commit — drift stays pinned
        self._tracer.record_span("drafts", "t1", 0, 1)       # near-miss
        with self._bracket("spec_commit", span="commit",     # unregistered
                           trace=None):
            pass

    def migrate_step(self):
        # migration near-miss: the registered name is `migrate`
        self._tracer.record_span("migrat", "t1", 0, 1)       # near-miss

    def gateway_step(self):
        # gateway near-misses: the registered kind is `gateway`, the
        # registered span names are gateway / ingress / quota
        self.telemetry.emit("gatway", "request.finished", step=1)  # typo
        self._tracer.record_span("ingres", "t1", 0, 1)           # near-miss

    def start_up(self):
        # the process's start-up ledger: the registered names are
        # startup.pool / startup.serving_init
        with startup_bracket("pool", span="startup.pol"):          # near-miss
            pass
        with LEDGER.startup_bracket("pool", span="pool"):          # no prefix
            pass

    @constructor_bracket("serving_init", span="startup.serving")   # near-miss
    def build(self):
        pass
