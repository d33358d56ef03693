"""The Granite-4.0-H family (``"family": "granite_hybrid"``): what the
harness takes from a configuration file whose ``model`` holds the keys of a
published ``granitemoehybrid`` ``config.json`` with ``num_local_experts``
0 (the dense members). Every function takes the configuration file; the
reference is ``perfbench/reference_granite_hybrid.py``.

The file of a model in the driver's catalog holds ``model``'s keys at its
top level too, value for value; the family refuses a file whose two copies
differ. What the source's config does not fix (``assumed``) and how the
random weights are drawn (``weights``: the program's own initialisers, and
``embedding_std``, the benchmark's choice for the tied embedding) are the
file's own keys.
"""

from perfbench import reference_granite_hybrid
from perfbench.byname import BenchError


def _checked(config_file: dict) -> dict:
    """``model``, held to what the program's family implements."""
    m = config_file["model"]
    fixed = {"model_type": "granitemoehybrid", "num_local_experts": 0,
             "num_experts_per_tok": 0, "position_embedding_type": "nope",
             "attention_bias": False, "mamba_proj_bias": False,
             "mamba_conv_bias": True, "mamba_n_groups": 1,
             "hidden_act": "silu", "normalization_function": "rmsnorm",
             "tie_word_embeddings": True}
    wrong = [f"{k} = {m.get(k)!r}" for k, v in fixed.items()
             if m.get(k) != v]
    if len(m["layer_types"]) != m["num_hidden_layers"]:
        wrong.append("layer_types is not one entry a layer")
    if m["mamba_n_heads"] * m["mamba_d_head"] != (m["mamba_expand"]
                                                  * m["hidden_size"]):
        wrong.append("mamba_n_heads x mamba_d_head is not mamba_expand x "
                     "hidden_size")
    if m["shared_intermediate_size"] != m["intermediate_size"]:
        wrong.append("shared_intermediate_size differs from "
                     "intermediate_size")
    if wrong:
        raise BenchError("the granite_hybrid family does not implement: "
                         f"{wrong}")
    apart = sorted(k for k in m if k in config_file and config_file[k] != m[k])
    if apart:
        raise BenchError(f"top-level {apart} differ from model's")
    return m


def _fields(config_file: dict) -> dict:
    """The program's ``GraniteHybridConfig`` fields."""
    m = _checked(config_file)
    same = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "mamba_n_heads",
            "mamba_d_head", "mamba_d_state", "mamba_n_groups", "mamba_d_conv",
            "mamba_chunk_size", "rms_norm_eps", "max_position_embeddings",
            "num_local_experts", "num_experts_per_tok")
    scalars = ("embedding_multiplier", "residual_multiplier",
               "attention_multiplier", "logits_scaling")
    return dict(layer_types=tuple(m["layer_types"]),
                intermediate_size=m["shared_intermediate_size"],
                embedding_std=float(config_file["weights"]["embedding_std"]),
                **{k: m[k] for k in same},
                **{k: float(m[k]) for k in scalars})


def serving_module(config_file: dict, dtype):
    """The module ``init_inference`` serves; its parameters are made in
    the type they are served in."""
    try:
        from deepspeed_tpu.models.granite_hybrid import (
            GraniteHybridConfig, GraniteHybridForCausalLM)
    except ImportError as e:   # a program older than the family
        raise BenchError("this program cannot run the granite_hybrid "
                         f"family: {e}")

    return GraniteHybridForCausalLM(GraniteHybridConfig(
        **_fields(config_file), dtype=dtype, param_dtype=dtype))


def _no_training():
    raise BenchError(
        "the granite_hybrid family has no training cell: at 16 bytes a "
        "parameter one period of ten layers and an eighth of the vocabulary "
        "are 12.4 GB of one chip, and the chunked scan has no backward")


def training_model(config_file: dict, dtype, remat_policy: str):
    _no_training()


def vocab_size(config_file: dict) -> int:
    return config_file["model"]["vocab_size"]


def max_context(config_file: dict) -> int:
    """The longest context the model declares; a cell's traffic mix sizes
    the pool (``max_total``)."""
    return config_file["model"]["max_position_embeddings"]


def reference_shape(config_file: dict) -> dict:
    """What ``reference_granite_hybrid`` takes beside the parameters and
    ids."""
    m = _checked(config_file)
    return dict(
        heads=m["num_attention_heads"], kv_heads=m["num_key_value_heads"],
        eps=m["rms_norm_eps"], types=tuple(m["layer_types"]),
        ssm_heads=m["mamba_n_heads"], ssm_head=m["mamba_d_head"],
        ssm_state=m["mamba_d_state"],
        embedding_multiplier=float(m["embedding_multiplier"]),
        residual_multiplier=float(m["residual_multiplier"]),
        attention_multiplier=float(m["attention_multiplier"]),
        logits_scaling=float(m["logits_scaling"]))


def reference_logits(config_file: dict):
    """``f(params, input_ids [rows, T], at=None) -> [rows, T or len(at),
    vocab]`` float32, the plain reference over the program's own parameter
    tree, its head taken at the positions ``at`` where given. It compiles
    itself, a layer a program (``reference_granite_hybrid``'s
    ``logits_a_layer_a_program`` says why): call it as it is, not under
    ``jax.jit``."""
    return reference_granite_hybrid.logits_a_layer_a_program(
        reference_shape(config_file))


def train_flops_per_token(config_file: dict, seq_len: int) -> float:
    _no_training()


def attention_shapes(config_file: dict) -> dict:
    """What the kernels' arithmetic asks (``families/mimo_v2.py``):
    ``heads``; ``global``: the attention layers, whose keys and values the
    paged cache holds; ``window``: none; and ``ssm``: the Mamba-2 layers
    and what each keeps a decode slot (``heads`` matrices of ``head`` x
    ``state`` values and the convolution's last ``taps - 1`` rows of ``heads
    x head + 2 state``) and scans in chunks of ``chunk`` positions."""
    m = _checked(config_file)
    head = m["hidden_size"] // m["num_attention_heads"]
    attn = sum(1 for k in m["layer_types"] if k == "attention")
    kv = {"kv_heads": m["num_key_value_heads"], "k_dim": head, "v_dim": head}
    return {"heads": m["num_attention_heads"],
            "global": {"layers": attn, **kv, "window": 0},
            "window": {"layers": 0, **kv, "window": 0},
            "ssm": {"layers": len(m["layer_types"]) - attn,
                    "heads": m["mamba_n_heads"], "head": m["mamba_d_head"],
                    "state": m["mamba_d_state"], "taps": m["mamba_d_conv"],
                    "chunk": m["mamba_chunk_size"]}}
