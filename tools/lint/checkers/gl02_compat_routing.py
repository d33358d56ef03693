"""GL02 compat-routing.

The JAX APIs whose spelling has moved under this repo before flow through
``deepspeed_tpu/utils/compat.py`` and nowhere else, so the next move is a
one-file change:

- ``shard_map`` — ``compat.shard_map``.
- Pallas TPU ``CompilerParams`` — ``compat.tpu_compiler_params``.
- ``force_tpu_interpret_mode`` — ``compat.tpu_interpret_mode``.
- ``serialize_executable`` — ``aot/bundle.py`` is its one consumer
  (baselined with its justification).
- persistent-cache arming (``jax.config.update("jax_compilation_
  cache_dir", ...)``) — ``compat.arm_compilation_cache``, which alone
  decides where the cache lives.
"""

import ast
from typing import Iterable

from tools.lint.core import Checker, Finding, LintContext, dotted, register
from tools.lint.core import str_const

EXEMPT = ("deepspeed_tpu/utils/compat.py",)


def _is_exempt(relpath: str) -> bool:
    return any(relpath == e or relpath.endswith("/" + e) for e in EXEMPT)


@register
class CompatRouting(Checker):
    code = "GL02"
    name = "compat-routing"
    description = ("routed JAX APIs (shard_map, CompilerParams, "
                   "interpret mode, serialize_executable, persistent-"
                   "cache arming) are forbidden outside utils/compat.py")

    def run(self, ctx: LintContext) -> Iterable[Finding]:
        for mod in ctx.modules:
            if _is_exempt(mod.relpath):
                continue
            # raw-source pre-filter: most files mention none of the
            # forbidden APIs and are never parsed at all
            if not mod.mentions("shard_map", "CompilerParams",
                                "serialize_executable",
                                "force_tpu_interpret_mode",
                                "compilation_cache"):
                continue
            # nested Attribute chains can match a prefix rule more than
            # once at the same spot — report each (line, message) once
            seen = set()
            for f in self._check_module(mod):
                if f.key() not in seen:
                    seen.add(f.key())
                    yield f

    def _check_module(self, mod) -> Iterable[Finding]:
        for node in mod.nodes():
            if isinstance(node, ast.ImportFrom) and node.level == 0:
                yield from self._check_import(mod, node)
            elif isinstance(node, ast.Attribute):
                yield from self._check_attr(mod, node)
            elif isinstance(node, ast.Call):
                yield from self._check_call(mod, node)

    def _find(self, mod, node, api, route):
        return Finding(
            code=self.code, path=mod.relpath, line=node.lineno,
            col=node.col_offset,
            message=(f"direct use of {api} — route through "
                     f"deepspeed_tpu.utils.compat.{route} (the one door "
                     f"to this API)"))

    def _check_import(self, mod, node) -> Iterable[Finding]:
        m = node.module or ""
        names = {a.name for a in node.names}
        if (m == "jax" and "shard_map" in names) \
                or m.startswith("jax.experimental.shard_map") \
                or (m == "jax.experimental" and "shard_map" in names):
            yield self._find(mod, node, "shard_map", "shard_map")
        if m.startswith("jax.experimental.serialize_executable") \
                or (m == "jax.experimental"
                    and "serialize_executable" in names):
            yield self._find(mod, node, "serialize_executable",
                             "(no shim: aot/bundle is the one consumer)")
        if m.startswith("jax.experimental.pallas"):
            if "CompilerParams" in names:
                yield self._find(mod, node, "CompilerParams",
                                 "tpu_compiler_params")
            if "force_tpu_interpret_mode" in names:
                yield self._find(mod, node, "force_tpu_interpret_mode",
                                 "tpu_interpret_mode")

    def _check_attr(self, mod, node) -> Iterable[Finding]:
        d = dotted(node)
        if d is None:
            return
        if d == "jax.shard_map" or d.startswith("jax.experimental.shard_map"):
            yield self._find(mod, node, "shard_map", "shard_map")
        elif d.startswith("jax.experimental.serialize_executable"):
            yield self._find(mod, node, "serialize_executable",
                             "(no shim: aot/bundle is the one consumer)")
        elif d.endswith(".CompilerParams") and (
                "pltpu" in d or "pallas" in d or d.startswith("tpu.")):
            yield self._find(mod, node, "CompilerParams",
                             "tpu_compiler_params")
        elif d.endswith(".force_tpu_interpret_mode"):
            yield self._find(mod, node, "force_tpu_interpret_mode",
                             "tpu_interpret_mode")

    def _check_call(self, mod, node) -> Iterable[Finding]:
        d = dotted(node.func) or ""
        if d.endswith("config.update") and node.args:
            key = str_const(node.args[0]) or ""
            if "compilation_cache" in key:
                yield self._find(
                    mod, node, f"persistent-cache arming ({key!r})",
                    "arm_compilation_cache")
        elif "compilation_cache" in d and d.rsplit(".", 1)[-1] in (
                "set_cache_dir", "initialize_cache"):
            yield self._find(mod, node, "persistent-cache arming",
                             "arm_compilation_cache")
