"""The prefill chunks' chunked scan of the Mamba-2 layers
(``ops/ssd_chunk_scan.py``), a layer and a scan chunk of ``Q`` positions:

- operations: ``C B^T`` once for all heads (``2 Q Q N``), and a head
  ``(L * C B^T) (delta x)`` (``2 Q Q P``), the incoming state through ``C``
  and the state handed on (``2 Q P N`` each);
- bytes: ``x`` in and ``y`` out (``Q H P`` values each), ``B``, ``C`` and
  ``delta`` in, and the state in and out once a PROGRAM CALL (float32
  between a call's chunks is the implementation's; the pool's two bytes a
  value are what the algorithm moves).

The larger of operations over peak and bytes over bandwidth; at the
published sizes (``Q`` 256, 64 heads of 64, state 128) and a call of 512
positions the bytes bind by a fifth: 2.18 GFLOP (11.1 us) against 10.8 MB
(13.2 us) a layer. One matched event is one layer of one
program call, which scans the call's whole width (``prefill_chunk_tokens``
in whole scan chunks: a padded position is work the call as dispatched
cannot skip; the padding is the engine's, not the kernel's)."""


def least_seconds(spec: dict, facts: dict, count: int, peak: dict):
    cell = facts["cell"]
    ssm = cell["family"].attention_shapes(cell["config_file"]).get("ssm")
    width = int(cell["serve"]["serving"].get("prefill_chunk_tokens") or 0)
    if not ssm or not width:
        return None
    q, h, p, n = ssm["chunk"], ssm["heads"], ssm["head"], ssm["state"]
    chunks = -(-width // q)
    ops = chunks * (2.0 * q * q * n + h * (2.0 * q * q * p + 4.0 * q * p * n))
    itemsize = 2
    nbytes = itemsize * (chunks * (2 * q * h * p + 2 * q * n + q * h)
                         + 2 * h * p * n)
    return count * max(ops / peak["bf16_flops_per_s"],
                       nbytes / peak["hbm_bytes_per_s"])
