"""AOT program-bundle format: content-addressed executables + manifest.

A bundle is a set of ``aot_``-prefixed files living flat in its
directory (the checkpoint tag dir when riding a checkpoint — flat on
purpose: the tiered/integrity engines' staging seams address files as
``<save_dir>/<tag>/<name>``)::

    <tag>/
      aot_manifest.json        # identity + program index
      aot_<sha16>.bin          # one blob per compiled program

Each blob is the pickled ``(payload, in_tree, out_tree)`` triple from
``jax.experimental.serialize_executable.serialize`` — everything
``deserialize_and_load`` needs. Blobs are content-addressed (file name =
first 16 hex chars of the blob's sha256) and the manifest records the
full hash, so a torn or bit-rotted blob is detected before any native
deserialization touches it (the same trust chain PR 3's integrity layer
gives payload files — and when the bundle rides a checkpoint, the
integrity manifest hashes these files too).

The manifest pins the four-part cache key from ISSUE 8: jax/jaxlib
version, topology fingerprint (mesh axes included — executables bind
device placement), per-program signature hash (argument treedef +
shapes + dtypes + shardings, ``jit_watch.signature_fingerprint``), and
the tuned-config hash (a program compiled under one set of tuned tiles
must not serve dispatch under another). ``verify_manifest`` diffs all
of them against the live runtime; any mismatch disables the bundle
loudly — stale executables fall back to compilation, never to wrong
programs.
"""

import hashlib
import json
import os
import pickle
from typing import Dict, List, Optional

from deepspeed_tpu.utils.fingerprint import diff_fingerprint
from deepspeed_tpu.utils.logging import logger

AOT_BUNDLE_VERSION = 1
AOT_MANIFEST_NAME = "aot_manifest.json"


# ----------------------------------------------------------------------
# per-program serialization
def serialize_compiled(compiled) -> bytes:
    """One compiled executable -> self-contained blob bytes."""
    from jax.experimental import serialize_executable

    payload, in_tree, out_tree = serialize_executable.serialize(compiled)
    return pickle.dumps((payload, in_tree, out_tree),
                        protocol=pickle.HIGHEST_PROTOCOL)


def deserialize_compiled(blob: bytes):
    """Blob bytes -> callable loaded executable."""
    from jax.experimental import serialize_executable

    payload, in_tree, out_tree = pickle.loads(blob)
    return serialize_executable.deserialize_and_load(payload, in_tree,
                                                     out_tree)


def blob_name(blob: bytes) -> str:
    return "aot_" + hashlib.sha256(blob).hexdigest()[:16] + ".bin"


# ----------------------------------------------------------------------
# manifest
def build_manifest(programs: List[Dict], fingerprint: Dict,
                   fingerprint_hash: str, tuned_hash: str) -> Dict:
    """``programs``: ``[{"name", "sig_hash", "file", "sha256", "size"}]``."""
    return {
        "version": AOT_BUNDLE_VERSION,
        "fingerprint": fingerprint,
        "fingerprint_hash": fingerprint_hash,
        "tuned_hash": tuned_hash,
        "programs": sorted(programs, key=lambda p: (p["name"],
                                                    p["sig_hash"])),
    }


def verify_manifest(manifest: Dict, current: Dict) -> List[Dict]:
    """Diff a bundle's identity against the live runtime's
    (``current``: the dict :func:`deepspeed_tpu.aot.capture.
    current_bundle_identity` builds). Returns a list of structured
    mismatches — empty means the bundle may pre-populate dispatch."""
    from deepspeed_tpu.utils.fingerprint import (fingerprint_hash,
                                                 normalize_mesh_axes)

    def norm_fp(fp: Optional[Dict]) -> Dict:
        # mesh axes compare in normalized form (alias-folded, size-1
        # dropped): a bundle stamped under the pre-3-axis names
        # ("model", no "fsdp") still names the same physical
        # partitioning today, and must not be rejected for the rename
        fp = dict(fp or {})
        if "mesh_axes" in fp:
            fp["mesh_axes"] = normalize_mesh_axes(fp["mesh_axes"])
        return fp

    mismatches: List[Dict] = []
    if manifest.get("version") != AOT_BUNDLE_VERSION:
        mismatches.append({"field": "version",
                           "saved": manifest.get("version"),
                           "current": AOT_BUNDLE_VERSION})
    saved_fp = norm_fp(manifest.get("fingerprint"))
    cur_fp = norm_fp(current.get("fingerprint"))
    # hash equality is judged over the NORMALIZED fingerprints (the
    # stored hash strings bind the axis spelling of whoever wrote
    # them), BUT the manifest's own hash must still agree with its own
    # fingerprint dict — a doctored/foreign hash is an identity
    # mismatch even when the dicts happen to line up
    stored_ok = manifest.get("fingerprint_hash") == fingerprint_hash(
        manifest.get("fingerprint") or {})
    if not stored_ok or fingerprint_hash(saved_fp) != \
            fingerprint_hash(cur_fp):
        mismatches.append({"field": "fingerprint_hash",
                           "saved": manifest.get("fingerprint_hash"),
                           "current": current.get("fingerprint_hash")})
    if manifest.get("tuned_hash") != current.get("tuned_hash"):
        mismatches.append({"field": "tuned_hash",
                           "saved": manifest.get("tuned_hash"),
                           "current": current.get("tuned_hash")})
    # the fingerprint dict itself, field by field, so the log names WHAT
    # changed (jaxlib? mesh axes? device kind?) instead of two hashes
    fp_diff = diff_fingerprint(saved_fp, cur_fp)
    for k, v in fp_diff.items():
        mismatches.append({"field": f"fingerprint.{k}", **v})
    return mismatches


def format_mismatches(mismatches: List[Dict]) -> str:
    return "\n".join(f"  {m['field']}: saved={m.get('saved')} -> "
                     f"current={m.get('current')}" for m in mismatches)


# ----------------------------------------------------------------------
# reading
def read_bundle(bundle_dir: str) -> Optional[Dict]:
    """The manifest of a bundle directory, or None when there is no
    bundle. A present-but-unreadable manifest is loud (a torn AOT
    record must not silently demote every future restart to cold
    compiles)."""
    path = os.path.join(bundle_dir, AOT_MANIFEST_NAME)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        try:
            return json.load(f)
        except ValueError as e:
            raise OSError(f"AOT bundle manifest {path!r} unreadable: {e}")


class BundleReader:
    """Lazy, hash-verified access to a bundle's program blobs."""

    def __init__(self, bundle_dir: str, manifest: Optional[Dict] = None):
        self.dir = bundle_dir
        self.manifest = manifest if manifest is not None \
            else read_bundle(bundle_dir)
        if self.manifest is None:
            raise FileNotFoundError(
                f"no {AOT_MANIFEST_NAME} in {bundle_dir!r}")
        self._index: Dict[tuple, Dict] = {
            (p["name"], p["sig_hash"]): p
            for p in self.manifest.get("programs", [])}

    def __len__(self):
        return len(self._index)

    def programs(self) -> List[Dict]:
        return list(self.manifest.get("programs", []))

    def contains(self, name: str, sig_hash: str) -> bool:
        return (name, sig_hash) in self._index

    def read_blob(self, name: str, sig_hash: str) -> bytes:
        """The verified blob bytes for one program. Hash mismatch (bit
        rot, torn write) raises ``OSError`` BEFORE any native
        deserialization sees the bytes."""
        entry = self._index[(name, sig_hash)]
        path = os.path.join(self.dir, entry["file"])
        with open(path, "rb") as f:
            blob = f.read()
        digest = hashlib.sha256(blob).hexdigest()
        if digest != entry["sha256"]:
            raise OSError(
                f"AOT blob {path!r} hash mismatch (manifest "
                f"{entry['sha256'][:16]}..., file {digest[:16]}...) — "
                "refusing to deserialize corrupt executable bytes")
        return blob

    def verify_all(self) -> List[str]:
        """Re-hash every blob; returns the list of bad entries (missing
        or mismatched), empty when the bundle is intact. The
        ``tools/aot_pack.py --verify`` body."""
        bad = []
        for (name, sig_hash), entry in sorted(self._index.items()):
            try:
                self.read_blob(name, sig_hash)
            except (OSError, KeyError) as e:
                bad.append(f"{name}[{sig_hash}]: {e}")
                logger.warning(f"[aot] {e}")
        return bad
