"""Operations and bytes computed from shapes: the benchmark's own copy, so
that a utilization cannot drift with the program.

``train_flops_per_token`` is the formula of
``deepspeed_tpu/profiling/flops_profiler.transformer_flops_per_token``
(6N + 12 L T d per token: forward 2N + 4 L T d, backward twice that;
recomputed operations are not counted). The kernel functions give what the
ALGORITHM needs, not what an implementation happens to do.
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


def gpt2_param_count(model: dict) -> int:
    """Parameters of a GPT-2 of these sizes, tied head counted once."""
    d, layers = model["n_embd"], model["n_layer"]
    per_layer = (d * 3 * d + 3 * d      # c_attn
                 + d * d + d            # attn c_proj
                 + d * 4 * d + 4 * d    # c_fc
                 + 4 * d * d + d        # mlp c_proj
                 + 4 * d)               # ln_1, ln_2
    return (model["vocab_size"] * d + model["n_positions"] * d
            + layers * per_layer + 2 * d)


def train_flops_per_token(model: dict, seq_len: int) -> float:
    n = gpt2_param_count(model)
    return 6.0 * n + 12.0 * model["n_layer"] * seq_len * model["n_embd"]


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
