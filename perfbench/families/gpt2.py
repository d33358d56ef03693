"""The GPT-2 family (``"family": "gpt2"``): what the harness takes from a
configuration file whose ``model`` holds the keys of a published GPT-2
``config.json``. Every function takes the configuration file; the
reference is ``perfbench/reference_gpt2.py``.

A family is this set of functions and nothing else: the jobs, the readers
and the kernels' arithmetic know a model only through them (README.md).
``param_count`` is the family's own, for ``train_flops_per_token``.
"""

from perfbench import reference_gpt2


def _fields(config_file: dict) -> dict:
    """The program's ``GPT2Config`` fields."""
    m = config_file["model"]
    if m.get("activation_function", "gelu_new") != "gelu_new":
        raise ValueError("the GPT-2 family uses gelu_new")
    return dict(vocab_size=m["vocab_size"], n_positions=m["n_positions"],
                n_embd=m["n_embd"], n_layer=m["n_layer"], n_head=m["n_head"],
                layer_norm_epsilon=m["layer_norm_epsilon"],
                activation="gelu", scan_layers=True)


def serving_module(config_file: dict, dtype):
    """The module ``init_inference`` serves."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2LMHeadModel

    return GPT2LMHeadModel(GPT2Config(**_fields(config_file), dtype=dtype))


def training_model(config_file: dict, dtype, remat_policy: str):
    """The object ``deepspeed_tpu.initialize`` trains."""
    from deepspeed_tpu.models.gpt2 import GPT2Config, GPT2ForTraining

    return GPT2ForTraining(GPT2Config(
        **_fields(config_file), dtype=dtype, remat=True,
        remat_policy=remat_policy))


def vocab_size(config_file: dict) -> int:
    """Token ids the traffic draws from: ``[0, vocab_size)``."""
    return config_file["model"]["vocab_size"]


def max_context(config_file: dict) -> int:
    """The longest context the model can be served or trained at."""
    return config_file["model"]["n_positions"]


def reference_logits(config_file: dict):
    """``f(params, input_ids [rows, T]) -> [rows, T, vocab]`` float32, the
    plain reference over the program's own parameter tree; jittable."""
    n_head = config_file["model"]["n_head"]
    return lambda params, ids: reference_gpt2.logits(params, ids, n_head)


def reference_loss(config_file: dict):
    """``f(params, input_ids) -> (sum of next-token negative
    log-likelihoods, token count)``: sums, so callers add blocks of rows."""
    n_head = config_file["model"]["n_head"]
    return lambda params, ids: reference_gpt2.next_token_loss(
        params, ids, n_head)


def param_count(config_file: dict) -> int:
    """Parameters at these sizes, the tied head counted once."""
    m = config_file["model"]
    d, layers = m["n_embd"], m["n_layer"]
    per_layer = (d * 3 * d + 3 * d      # c_attn
                 + d * d + d            # attn c_proj
                 + d * 4 * d + 4 * d    # c_fc
                 + 4 * d * d + d        # mlp c_proj
                 + 4 * d)               # ln_1, ln_2
    return (m["vocab_size"] * d + m["n_positions"] * d
            + layers * per_layer + 2 * d)


def train_flops_per_token(config_file: dict, seq_len: int) -> float:
    """6N + 12 L T d: the formula of ``deepspeed_tpu/profiling/
    flops_profiler.transformer_flops_per_token`` (forward 2N + 4 L T d,
    backward twice that; recomputed operations are not counted)."""
    m = config_file["model"]
    return (6.0 * param_count(config_file)
            + 12.0 * m["n_layer"] * seq_len * m["n_embd"])


def attention_shapes(config_file: dict) -> dict:
    """What the kernels' arithmetic asks: ``heads`` and ``head_dim`` of an
    attention call, ``kv_heads`` and the ``paged_layers`` that keep keys
    and values in the paged cache."""
    m = config_file["model"]
    return {"heads": m["n_head"], "kv_heads": m["n_head"],
            "head_dim": m["n_embd"] // m["n_head"],
            "paged_layers": m["n_layer"]}
