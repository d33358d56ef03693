"""GL01 jax-free-host-modules.

The serving policy tier (scheduler/router/health + the block/prefix-
cache bookkeeping), the telemetry event model and the tuned-config
artifact are pure host code by contract: a module-level ``import jax``
there puts device-library import latency inside every ``admit()`` and
drags jax into the millisecond tier-1 host tests. The invariant was
previously pinned ad hoc in ``tests/unit/test_router.py``; this checker
is now the single registry, and that test is a thin wrapper over it.

The walk follows the **module-level** import closure through real
``deepspeed_tpu`` module files (package ``__init__`` roots are exempt —
their jax pulls are lazy by contract, behind ``__getattr__`` and
function boundaries), flagging the first edge that reaches
``jax``/``jaxlib``/``flax``.
"""

import ast
import os
from typing import Iterable, List, Optional, Tuple

from tools.lint.core import Checker, Finding, LintContext, register

# The registry: package-root-relative posix paths that must stay
# jax-free at import time (tests/unit/test_router.py wraps this).
JAX_FREE_MODULES = (
    "deepspeed_tpu/serving/scheduler.py",
    "deepspeed_tpu/serving/router.py",
    "deepspeed_tpu/serving/health.py",
    "deepspeed_tpu/serving/blocks.py",
    "deepspeed_tpu/serving/prefix_cache.py",
    "deepspeed_tpu/serving/config.py",
    "deepspeed_tpu/serving/request.py",
    "deepspeed_tpu/serving/spec_decode.py",
    "deepspeed_tpu/serving/autoscaler.py",
    "deepspeed_tpu/serving/replay.py",
    "deepspeed_tpu/serving/capacity.py",
    "deepspeed_tpu/serving/migration.py",
    "deepspeed_tpu/serving/gateway.py",
    "deepspeed_tpu/serving/tenancy.py",
    "deepspeed_tpu/telemetry/events.py",
    "deepspeed_tpu/telemetry/tracing.py",
    "deepspeed_tpu/telemetry/process_ledger.py",
    "deepspeed_tpu/telemetry/compile_watch.py",
    "deepspeed_tpu/telemetry/metrics.py",
    "deepspeed_tpu/telemetry/registry.py",
    "deepspeed_tpu/telemetry/prom.py",
    "deepspeed_tpu/telemetry/flightrec.py",
    "deepspeed_tpu/autotuning/artifact.py",
)

DEVICE_TOPLEVEL = ("jax", "jaxlib", "flax")
PACKAGE = "deepspeed_tpu"


def module_imports(tree: ast.Module, mod_name: str) -> List[Tuple[str, int]]:
    """(imported module name, line) pairs at module level only."""
    out = []
    for node in tree.body:
        if isinstance(node, ast.Import):
            out.extend((a.name, node.lineno) for a in node.names)
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = mod_name.split(".")[:-node.level]
                mod = ".".join(base + ([node.module] if node.module else []))
            else:
                mod = node.module or ""
            if not mod:
                continue
            out.append((mod, node.lineno))
            # `from pkg import mod` pulls pkg.mod when that is a module
            out.extend((f"{mod}.{a.name}", node.lineno) for a in node.names)
    return out


def _mod_file(root: str, name: str) -> Optional[str]:
    rel = name.split(".")
    path = os.path.join(root, *rel)
    if os.path.isfile(path + ".py"):
        return path + ".py"
    if os.path.isdir(path):
        return os.path.join(path, "__init__.py")
    return None


@register
class JaxFreeHostModules(Checker):
    code = "GL01"
    name = "jax-free-host-modules"
    description = ("registered host-policy modules (and their module-"
                   "level import closure) must not reach jax/jaxlib/"
                   "flax at import time")

    def run(self, ctx: LintContext) -> Iterable[Finding]:
        # shared across entries: a bad import line in a util reached
        # from N registered modules is ONE finding (one fix), not N
        flagged = set()
        for entry in JAX_FREE_MODULES:
            start = ctx.parse_under_root(entry)
            if start is None:
                continue
            yield from self._walk(ctx, entry, start, flagged)

    def _walk(self, ctx, entry, start, flagged) -> Iterable[Finding]:
        start_name = entry[:-3].replace("/", ".")
        seen = set()
        # (module name, ModuleInfo, via-chain of names)
        stack = [(start_name, start, ())]
        while stack:
            name, mod, chain = stack.pop()
            if name in seen or mod is None or mod.tree() is None:
                continue
            seen.add(name)
            for imp, line in module_imports(mod.tree(), name):
                top = imp.split(".")[0]
                if top in DEVICE_TOPLEVEL:
                    if (mod.relpath, line) in flagged:
                        continue
                    flagged.add((mod.relpath, line))
                    via = " -> ".join(chain + (name,))
                    yield Finding(
                        code=self.code, path=mod.relpath, line=line, col=0,
                        message=(f"{entry} must stay jax-free at import "
                                 f"time but reaches '{imp}' via {via} — "
                                 f"move the import behind a function "
                                 f"boundary or drop the dependency"))
                    continue
                if top != PACKAGE:
                    continue  # numpy/pydantic/stdlib: fine
                path = _mod_file(ctx.root, imp)
                if path is None or path.endswith("__init__.py"):
                    # package roots are lazy by contract
                    continue
                rel = os.path.relpath(path, ctx.root).replace(os.sep, "/")
                stack.append((imp, ctx.parse_under_root(rel),
                              chain + (name,)))
