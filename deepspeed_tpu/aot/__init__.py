"""AOT program cache: serialize the engines' steady-state compiled
executables and ship them with checkpoints, so an elastic restart on the
same topology reaches its first step without recompiling the world.

The telemetry layer's :class:`~deepspeed_tpu.telemetry.jit_watch.
WatchedFunction` already compiles ahead-of-time and holds the compiled
executables; this package is the persistence tier on top:

- ``bundle``  — the on-disk format: a content-addressed blob per
  program (``jax.experimental.serialize_executable``) plus a manifest
  keyed by (jaxlib version, topology fingerprint, program signature,
  tuned-config hash);
- ``capture`` — engine-facing capture (walk the live watched functions,
  serialize every cached executable) and restore (:class:`AOTStore`
  pre-populates dispatch: a watched function consults the store before
  paying ``lower().compile()``).
"""

from deepspeed_tpu.aot.bundle import (AOT_BUNDLE_VERSION,
                                      AOT_MANIFEST_NAME, BundleReader,
                                      build_manifest, deserialize_compiled,
                                      read_bundle, serialize_compiled,
                                      verify_manifest)
from deepspeed_tpu.aot.capture import (AOTStore, capture_entries,
                                       current_bundle_identity, load_bundle,
                                       save_bundle)

__all__ = [
    "AOT_BUNDLE_VERSION", "AOT_MANIFEST_NAME", "AOTStore",
    "BundleReader", "build_manifest", "capture_entries",
    "current_bundle_identity", "deserialize_compiled", "load_bundle",
    "read_bundle", "save_bundle", "serialize_compiled", "verify_manifest",
]
