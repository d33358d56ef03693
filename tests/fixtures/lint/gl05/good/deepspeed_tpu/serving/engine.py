"""GOOD: registered kinds everywhere; dynamic kinds are the emitting
wrapper's responsibility and are not flagged."""

from deepspeed_tpu.telemetry.events import make_event


class ServingEngine:
    def step(self, kind_from_config):
        self.telemetry.emit("serving", "step.gauges", step=1)
        self._telemetry.emit("fault", "watchdog.hang", step=1)
        self.telemetry.emit(kind_from_config, "dynamic", step=1)
        return make_event("compile", "x", 0, 0, {})

    def trace(self, name_from_caller):
        self.telemetry.emit("span", "queue", step=1)
        self._tracer.record_span("decode", "t1", 0, 1)
        self._tracer.record_span(name_from_caller, "t1", 0, 1)  # dynamic
        with self._bracket("anything.goes", span="request"):
            pass
        with self._bracket("decode.dispatch"):  # annotation only: no span
            pass
        self.telemetry.step_trace.mark("queue", 0, 1)

    def spec_step(self):
        # speculative decoding's registered span names
        with self._bracket("draft", span="draft", trace=None):
            pass
        self._tracer.record_span("verify", "t1", 0, 1)
        with self._bracket("spec_commit", span="spec_commit", trace=None):
            pass

    def migrate_step(self):
        # live KV migration's registered span name
        self._tracer.record_span("migrate", "t1", 0, 1)

    def gateway_step(self):
        # the HTTP front door's registered kind + span names
        self.telemetry.emit("gateway", "request.finished", step=1)
        self._tracer.begin("gateway", "t1")
        self._tracer.record_span("ingress", "t1", 0, 1)
        self._tracer.record_span("quota", "t1", 0, 1)

    def start_up(self):
        # the process's start-up ledger's registered span names
        with startup_bracket("pool", span="startup.pool"):
            pass
        with LEDGER.startup_bracket("anything", span="startup.pool"):
            pass

    @constructor_bracket("serving_init", span="startup.serving_init")
    def build(self):
        pass
