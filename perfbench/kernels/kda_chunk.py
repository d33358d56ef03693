"""The prefill chunks' carry of the KDA layers' state through the
sub-chunks (``ops/kda_chunk.py``, the kernel ``kda_chunk``), a layer and a
program call of ``T`` positions in ``n = T / C`` sub-chunks of ``C``:

- operations, a head and sub-chunk: the incoming state through ``[T Kbar |
  Qbar - B T Kbar]`` (``2 x 2C x K x V``) and the outgoing state's rank-``C``
  update ``Khat^T w`` (``2 x C x K x V``): ``6 C K V``, counted ONCE
  against the chip's stated (bfloat16) peak: the kernel takes them in
  float32 at ``highest`` precision, six bfloat16 passes a product on this
  chip, and that is the kernel's own choice, which the share has to show
  as cost and not to count as work the algorithm needs;
- bytes: what the kernel reads and writes a sub-chunk (``[2C, K]``, ``[2C,
  V]``, ``[C, K]``, ``[1, K]`` in, ``[C, V]`` out, float32) and the state in
  and out once a CALL (``2 K V`` float32 a head).

The larger of the two. What is matched is the kernel ALONE: the terms it
reads are made by XLA einsums and one triangular solve a call
(``kda._sub_chunk_terms``), which are no part of this count nor of the
matched time. One matched event is one layer of one program call, which
carries the call's whole width (``prefill_chunk_tokens``: a padded position
is work the call as dispatched cannot skip)."""


def least_seconds(spec: dict, facts: dict, count: int, peak: dict):
    cell = facts["cell"]
    kda = cell["family"].attention_shapes(cell["config_file"]).get("kda")
    width = int(cell["serve"]["serving"].get("prefill_chunk_tokens") or 0)
    if not kda or not width:
        return None
    c, h, k, v = kda["sub_chunk"], kda["heads"], kda["key"], kda["value"]
    n = -(-width // c)
    ops = h * n * 6.0 * c * k * v
    nbytes = 4.0 * h * (n * (3 * c * k + 3 * c * v + k) + 2 * k * v)
    return count * max(ops / peak["bf16_flops_per_s"],
                       nbytes / peak["hbm_bytes_per_s"])
