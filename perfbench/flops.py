"""Published peaks, and the operations and bytes of kernels computed from
shapes: the benchmark's own copy, so that a utilization cannot drift with
the program. The kernel functions take shapes and no model, and give what
the ALGORITHM needs, not what an implementation happens to do. What a
model needs per token (parameters, training operations) is its family's
(``perfbench/families/``).
"""

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind``
    (``perfbench/peaks.json``). An unknown kind is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r} in "
            f"perfbench/peaks.json (known: {sorted(table)}); add the kind "
            "with its source, nothing is assumed for an unknown chip")
    return table[device_kind]


def flash_train_flops(batch: int, heads: int, seq: int, head_dim: int,
                      causal: bool = True) -> float:
    """Forward + backward of one attention call. Forward: QK^T and PV,
    2 * 2 * T * T * D per head; backward: dV, dP, dQ, dK, twice that
    (the recomputation of S inside a flash backward is not counted).
    Causal halves the useful area."""
    full = 4.0 * batch * heads * seq * seq * head_dim
    return 3.0 * full * (0.5 if causal else 1.0)


def flash_train_bytes(batch: int, heads: int, seq: int, head_dim: int,
                      itemsize: int = 2) -> float:
    """Least HBM traffic of forward + backward: forward reads q, k, v and
    writes o; backward reads q, k, v, o, do and writes dq, dk, dv."""
    return 12.0 * batch * heads * seq * head_dim * itemsize


def paged_decode_bytes(live_tokens: int, layers_in_call: int,
                       kv_heads: int, head_dim: int,
                       itemsize: int = 2) -> float:
    """KV bytes one paged decode-attention call has to read: keys and
    values of every live token of the batch."""
    return 2.0 * live_tokens * layers_in_call * kv_heads * head_dim * itemsize
