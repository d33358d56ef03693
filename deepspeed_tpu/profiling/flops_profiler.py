"""FLOPs profiler.

Capability parity with the reference ``FlopsProfiler``
(``profiling/flops_profiler/profiler.py:17``), which monkey-patches
``torch.nn.functional`` to count MACs as ops execute (``:806,861``) and hangs
latency hooks on every module. Under XLA none of that is necessary or
meaningful: the compiler knows the exact FLOP count of the compiled program.
This profiler asks XLA (``jit(fn).lower(...).compile().cost_analysis()``)
and pairs it with measured step latency to report FLOPS utilisation, plus an
analytic per-component breakdown for transformer models (the reference's
per-module tree) derived from the model config rather than hooks.
"""

import time
from typing import Any, Callable, Dict, Optional

import jax
import numpy as np

from deepspeed_tpu.utils.logging import logger


def number_to_string(num, units=None, precision=2):
    if units is None:
        if num >= 1e12:
            return f"{num / 1e12:.{precision}f} T"
        if num >= 1e9:
            return f"{num / 1e9:.{precision}f} G"
        if num >= 1e6:
            return f"{num / 1e6:.{precision}f} M"
        if num >= 1e3:
            return f"{num / 1e3:.{precision}f} K"
        return f"{num:.{precision}f} "
    return f"{num:.{precision}f} {units}"


def flops_to_string(flops, units=None, precision=2):
    return number_to_string(flops, units, precision) + "FLOPS"


def params_to_string(params_num, units=None, precision=2):
    return number_to_string(params_num, units, precision).rstrip() or "0"


def duration_to_string(duration, units=None, precision=2):
    if duration >= 1:
        return f"{duration:.{precision}f} s"
    if duration >= 1e-3:
        return f"{duration * 1e3:.{precision}f} ms"
    return f"{duration * 1e6:.{precision}f} us"


def count_params(params) -> int:
    return int(sum(np.prod(l.shape) for l in jax.tree_util.tree_leaves(params)
                   if hasattr(l, "shape")))


class FlopsProfiler:
    """Profile a jitted step function.

    Usage (mirrors reference ``profiler.py`` API surface)::

        prof = FlopsProfiler(model=engine)
        prof.start_profile()
        engine.train_batch(batch=batch)     # or any fn via profile_fn
        prof.stop_profile()
        prof.print_model_profile()
    """

    def __init__(self, model=None, ds_engine=None):
        self.engine = ds_engine if ds_engine is not None else model
        self.started = False
        self._t0 = None
        self.flops = 0
        self.macs = 0
        self.params = 0
        self.duration = 0.0
        self.cost: Dict[str, float] = {}

    # ------------------------------------------------------------------
    def profile_fn(self, fn: Callable, *args, **kwargs):
        """Profile one callable: returns (flops, duration_s, cost_dict).

        Times the *compiled* executable (warm call), matching the program
        the FLOP count refers to.
        """
        jfn = jax.jit(fn)
        compiled = jfn.lower(*args, **kwargs).compile()
        cost = compiled.cost_analysis()
        if isinstance(cost, (list, tuple)):
            cost = cost[0] if cost else {}
        self.cost = dict(cost or {})
        self.flops = int(self.cost.get("flops", 0.0))
        self.macs = self.flops // 2
        jax.block_until_ready(jfn(*args, **kwargs))  # warm (compile cache)
        t0 = time.perf_counter()
        jax.block_until_ready(jfn(*args, **kwargs))
        self.duration = time.perf_counter() - t0
        return self.flops, self.duration, self.cost

    # reference start/stop surface around an engine step
    def start_profile(self, ignore_list=None):
        self.started = True
        if self.engine is not None and getattr(self.engine, "state", None) is not None:
            self.params = count_params(self.engine.state.params)
        self._t0 = time.perf_counter()

    def stop_profile(self):
        if not self.started:
            return
        self.duration = time.perf_counter() - self._t0
        eng = self.engine
        if eng is not None and getattr(eng, "_jit_micro", None) is not None \
                and getattr(eng, "state", None) is not None \
                and getattr(eng, "_last_batch", None) is not None:
            try:
                # lower through the engine's own jit wrapper so shardings/
                # donation match; one extra compile, paid only at the
                # profile step. Total step FLOPs = gas micro-steps + apply.
                gas = getattr(eng, "gradient_accumulation_steps", lambda: 1)()
                micro = eng._jit_micro.lower(
                    eng.state, eng._last_batch).compile().cost_analysis()
                if isinstance(micro, (list, tuple)):
                    micro = micro[0] if micro else {}
                self.cost = dict(micro or {})
                flops = int(self.cost.get("flops", 0.0)) * int(gas)
                if getattr(eng, "_jit_apply", None) is not None:
                    import jax.numpy as jnp

                    apply_cost = eng._jit_apply.lower(
                        eng.state, jnp.zeros((), jnp.float32)
                    ).compile().cost_analysis()
                    if isinstance(apply_cost, (list, tuple)):
                        apply_cost = apply_cost[0] if apply_cost else {}
                    flops += int((apply_cost or {}).get("flops", 0.0))
                self.flops = flops
                self.macs = flops // 2
            except Exception as e:  # cost analysis is best-effort
                logger.warning(f"flops cost analysis unavailable: {e}")
        self.started = False

    def end_profile(self):
        self.started = False

    def reset_profile(self):
        self.flops = self.macs = self.params = 0
        self.duration = 0.0
        self.cost = {}

    # ------------------------------------------------------------------
    def get_total_flops(self, as_string=False):
        return flops_to_string(self.flops) if as_string else self.flops

    def get_total_macs(self, as_string=False):
        return number_to_string(self.macs) + "MACs" if as_string else self.macs

    def get_total_params(self, as_string=False):
        return params_to_string(self.params) if as_string else self.params

    def get_total_duration(self, as_string=False):
        return duration_to_string(self.duration) if as_string else self.duration

    def print_model_profile(self, profile_step=1, module_depth=-1,
                            top_modules=1, detailed=True, output_file=None):
        lines = [
            "-------------------------- DeepSpeed-TPU Flops Profiler "
            "--------------------------",
            f"profile step:                   {profile_step}",
            f"params:                         {params_to_string(self.params)}",
            f"fwd+bwd flops (XLA measured):   {flops_to_string(self.flops)}",
            f"fwd+bwd MACs:                   {number_to_string(self.macs)}MACs",
            f"step latency:                   {duration_to_string(self.duration)}",
        ]
        if self.duration > 0 and self.flops:
            lines.append(
                f"achieved FLOPS:                 "
                f"{flops_to_string(self.flops / self.duration)}")
        for k in ("bytes accessed", "utilization"):
            if k in self.cost:
                lines.append(f"{k + ':':<32}{number_to_string(self.cost[k])}")
        report = "\n".join(lines)
        if output_file:
            with open(output_file, "w") as f:
                f.write(report + "\n")
        else:
            logger.info("\n" + report)
        return report


def transformer_flops_per_token(n_params: int, n_layer: int, n_embd: int,
                                seq_len: int) -> Dict[str, float]:
    """Analytic transformer cost model (PaLM appendix / scaling-book form):
    fwd ≈ 2N + 2·L·T·d per token, train ≈ 3x fwd. The reference derives its
    per-module tree from hooks; on TPU the analytic form is what MFU math
    uses."""
    fwd = 2.0 * n_params + 2.0 * 2.0 * n_layer * seq_len * n_embd
    return {"fwd_flops_per_token": fwd,
            "train_flops_per_token": 3.0 * fwd}


def get_model_profile(model, input_shape=None, args=None, kwargs=None,
                      print_profile=True, detailed=True, module_depth=-1,
                      top_modules=1, warm_up=1, as_string=True,
                      output_file=None, ignore_modules=None, rng=None):
    """Standalone profile of a flax module (reference ``get_model_profile``,
    ``profiler.py:1139``): returns ``(flops, macs, params)``."""
    import jax.numpy as jnp

    rng = rng if rng is not None else jax.random.PRNGKey(0)
    if args is None:
        if input_shape is None:
            raise ValueError("provide input_shape or args")
        args = (jnp.zeros(input_shape, jnp.int32),)
    kwargs = kwargs or {}
    variables = model.init(rng, *args, **kwargs)
    params = count_params(variables)

    def fwd(v, *a):
        return model.apply(v, *a, **kwargs)

    prof = FlopsProfiler()
    flops, duration, _ = prof.profile_fn(fwd, variables, *args)
    prof.params = params
    if print_profile:
        prof.print_model_profile(detailed=detailed, module_depth=module_depth,
                                 top_modules=top_modules,
                                 output_file=output_file)
    macs = flops // 2
    if as_string:
        return (flops_to_string(flops), number_to_string(macs) + "MACs",
                params_to_string(params))
    return flops, macs, params
