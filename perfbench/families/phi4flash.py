"""The Phi-4-mini-flash family (``"family": "phi4flash"``): what the harness
takes from a configuration file whose ``model`` holds the keys of a
published ``phi4flash`` ``config.json``. Every function takes the
configuration file; the reference is ``perfbench/reference_phi4flash.py``.

The file of a model in the driver's catalog holds ``model``'s keys at its
top level too, value for value; the family refuses a file whose two copies
differ. What the source's config does not fix (``assumed``: the Mamba
layers' sizes, the head size, the forms) and how the random weights are
drawn (``weights``: the program's own initialisers, and ``embedding_std``,
the benchmark's choice for the tied embedding) are the file's own keys.
"""

from perfbench import reference_phi4flash
from perfbench.byname import BenchError


def _checked(config_file: dict) -> dict:
    """``model``, held to what the program's family implements."""
    m = config_file["model"]
    fixed = {"model_type": "phi4flash", "mb_per_layer": 2,
             "hidden_act": "silu", "tie_word_embeddings": True,
             "mlp_bias": False, "lm_head_bias": False}
    wrong = [f"{k} = {m.get(k)!r}" for k, v in fixed.items()
             if m.get(k) != v]
    if m["num_hidden_layers"] % 2 or m["num_hidden_layers"] < 8:
        wrong.append("num_hidden_layers is not an even depth of 8 or more")
    if "head_dim" in m:
        wrong.append("head_dim is no key of the source's config (the head "
                     "is hidden_size / num_attention_heads: assumed)")
    if wrong:
        raise BenchError("the phi4flash family does not implement: "
                         f"{wrong}")
    apart = sorted(k for k in m if k in config_file and config_file[k] != m[k])
    if apart:
        raise BenchError(f"top-level {apart} differ from model's")
    return m


def _fields(config_file: dict) -> dict:
    """The program's ``Phi4FlashConfig`` fields: the source's, and the
    sizes the file assumes."""
    m = _checked(config_file)
    same = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "intermediate_size",
            "sliding_window", "mb_per_layer", "layer_norm_eps",
            "max_position_embeddings")
    sizes = config_file["assumed"]["sizes"]
    return dict(embedding_std=float(config_file["weights"]["embedding_std"]),
                mamba_d_state=sizes["d_state"], mamba_d_conv=sizes["d_conv"],
                mamba_expand=sizes["expand"], mamba_dt_rank=sizes["dt_rank"],
                **{k: m[k] for k in same})


def serving_module(config_file: dict, dtype):
    """The module ``init_inference`` serves; its parameters are made in
    the type they are served in."""
    try:
        from deepspeed_tpu.models.phi4flash import (Phi4FlashConfig,
                                                    Phi4FlashForCausalLM)
    except ImportError as e:   # a program older than the family
        raise BenchError("this program cannot run the phi4flash family: "
                         f"{e}")

    return Phi4FlashForCausalLM(Phi4FlashConfig(
        **_fields(config_file), dtype=dtype, param_dtype=dtype))


def _no_training():
    raise BenchError(
        "the phi4flash family has no training cell: at 16 bytes a parameter "
        "3.85G parameters are 61.6 GB, no cut within the guide's floors fits "
        "one chip, and the Mamba-1 scan has no backward")


def training_model(config_file: dict, dtype, remat_policy: str):
    _no_training()


def train_flops_per_token(config_file: dict, seq_len: int) -> float:
    _no_training()


def vocab_size(config_file: dict) -> int:
    return config_file["model"]["vocab_size"]


def max_context(config_file: dict) -> int:
    """The longest context the model declares; a cell's traffic mix sizes
    the pool (``max_total``)."""
    return config_file["model"]["max_position_embeddings"]


def kinds(config_file: dict) -> tuple:
    """Each layer's kind: even layers Mamba-shaped (a Mamba-1 up to the
    middle layer, gated memory units past it), odd ones attention (windows
    before the middle, the one full layer just past it, then cross
    layers)."""
    half = _checked(config_file)["num_hidden_layers"] // 2

    def kind(i):
        if i % 2 == 0:
            return "mamba" if i <= half else "gmu"
        if i < half:
            return "window"
        return "full" if i == half + 1 else "cross"

    return tuple(kind(i) for i in range(2 * half))


def reference_shape(config_file: dict) -> dict:
    """What ``reference_phi4flash`` takes beside the parameters and ids."""
    m = _checked(config_file)
    return dict(heads=m["num_attention_heads"],
                kv_heads=m["num_key_value_heads"], eps=m["layer_norm_eps"],
                window=m["sliding_window"],
                ssm_state=config_file["assumed"]["sizes"]["d_state"],
                kinds=kinds(config_file))


def reference_logits(config_file: dict, kept_states=()):
    """``f(params, input_ids [rows, T], at=None, real=None) -> [rows, T or
    len(at), vocab]`` float32 (numpy), the plain reference over the
    program's own parameter tree, its head taken at the positions ``at``
    where given; with ``real`` also the states of the Mamba layers at the
    places ``kept_states`` after position ``real - 1``. It compiles itself,
    a layer a program: call it as it is, not under ``jax.jit``."""
    return reference_phi4flash.logits_a_layer_a_program(
        {**reference_shape(config_file), "kept_states": tuple(kept_states)})


def attention_shapes(config_file: dict) -> dict:
    """What the kernels' arithmetic asks (``families/mimo_v2.py``):
    ``heads``; ``global``: the layers that READ the one shared pool in a
    decode step (the full layer and every cross layer: the pool is kept
    once and read by each); ``window``: the window layers and their window;
    ``ssm``: the Mamba-1 layers, their channels, states a channel and
    taps."""
    m = _checked(config_file)
    sizes = config_file["assumed"]["sizes"]
    layers = kinds(config_file)
    head = m["hidden_size"] // m["num_attention_heads"]
    kv = {"kv_heads": m["num_key_value_heads"], "k_dim": head, "v_dim": head}
    return {"heads": m["num_attention_heads"],
            "global": {"layers": layers.count("full") + layers.count("cross"),
                       **kv, "window": 0},
            "window": {"layers": layers.count("window"), **kv,
                       "window": m["sliding_window"]},
            "ssm": {"layers": layers.count("mamba"),
                    "channels": sizes["expand"] * m["hidden_size"],
                    "state": sizes["d_state"], "taps": sizes["d_conv"]}}
