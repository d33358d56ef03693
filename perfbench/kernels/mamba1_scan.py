"""The prefill chunks' scan of the Mamba-1 layers
(``ops/mamba1_scan.py:mamba1_chunk_scan``), a layer and a program call of
``T`` positions (``prefill_chunk_tokens``: a padded position is work the
call as dispatched cannot skip; the padding is the engine's):

- bytes: ``x`` and ``delta`` in and ``y`` out (``T x channels`` float32
  each), ``B`` and ``C`` (``T x states``) and the state in and out once a
  call (``channels x states`` float32);
- operations: a position, channel and state is an exponential, three
  multiplies and two adds on the vector unit; the matrix unit has no part
  in it, and the published peaks (``peaks.json``) hold no vector peak, so
  the bound is the bytes'. At the published sizes (512 x 5,120 x 16) that
  is 32.2 MB, 39 us a layer a call, under 293 M vector operations: the
  share this reads says how far the vector unit, not the memory, holds the
  kernel.

One matched event is one layer of one program call."""


def least_seconds(spec: dict, facts: dict, count: int, peak: dict):
    cell = facts["cell"]
    ssm = cell["family"].attention_shapes(cell["config_file"]).get("ssm")
    width = int(cell["serve"]["serving"].get("prefill_chunk_tokens") or 0)
    if not ssm or "channels" not in ssm or not width:
        return None
    c, n = ssm["channels"], ssm["state"]
    nbytes = 4 * (3 * width * c + 2 * width * n + 2 * c * n)
    return count * nbytes / peak["hbm_bytes_per_s"]
