"""The LFM2-MoE family (``"family": "lfm2_moe"``): what the harness takes
from a configuration file whose ``model`` holds the keys of a published
``lfm2_moe`` ``config.json``. Every function takes the configuration file;
the reference is ``perfbench/reference_lfm2_moe.py``.

A CUT file is cut in depth only (README.md): ``model.num_hidden_layers``
and ``model.layer_types`` are the source's first layers as they stand, and
every expert of every sparse layer and the whole vocabulary are held. The
file of a model in the driver's catalog holds ``model``'s keys at its top
level too, value for value; the family refuses a file whose two copies
differ. What the source's config does not fix (``assumed``) and the
scales of the random weights (``weights``) are the file's own keys.
"""

from perfbench import reference_lfm2_moe
from perfbench.byname import BenchError

# the top-k normalisation's ``+ eps`` (the file's ``assumed`` says why)
TOPK_NORM_EPS = 1e-6


def _checked(config_file: dict) -> dict:
    """``model``, held to what the program's family implements."""
    m = config_file["model"]
    fixed = {"conv_bias": False, "norm_topk_prob": True,
             "use_expert_bias": True, "model_type": "lfm2_moe"}
    wrong = [f"{k} = {m.get(k)!r}" for k, v in fixed.items()
             if m.get(k) != v]
    if len(m["layer_types"]) != m["num_hidden_layers"]:
        wrong.append("layer_types is not one entry a layer")
    if wrong:
        raise BenchError(f"the lfm2_moe family does not implement: {wrong}")
    apart = sorted(k for k in m if k in config_file and config_file[k] != m[k])
    if apart:
        raise BenchError(f"top-level {apart} differ from model's")
    return m


def _fields(config_file: dict) -> dict:
    """The program's ``Lfm2MoeConfig`` fields."""
    m, w = _checked(config_file), config_file.get("weights", {})
    scales = {k: float(w[k]) for k in ("conv_in_std", "conv_tap_std",
                                       "conv_out_std", "expert_bias_std")
              if k in w}
    return dict(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        layer_types=tuple(m["layer_types"]),
        num_dense_layers=m["num_dense_layers"],
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        num_experts=m["num_experts"],
        num_experts_per_tok=m["num_experts_per_tok"],
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        route_norm_eps=TOPK_NORM_EPS, conv_L_cache=m["conv_L_cache"],
        norm_eps=m["norm_eps"],
        rope_theta=float(m["rope_theta"]),
        max_position_embeddings=m["max_position_embeddings"], **scales)


def serving_module(config_file: dict, dtype):
    """The module ``init_inference`` serves; its parameters are made in
    the type they are served in."""
    try:
        from deepspeed_tpu.models.lfm2_moe import (Lfm2MoeConfig,
                                                   Lfm2MoeForCausalLM)
    except ImportError as e:   # a program older than the family
        raise BenchError(f"this program cannot run the lfm2_moe family: {e}")

    return Lfm2MoeForCausalLM(Lfm2MoeConfig(
        **_fields(config_file), dtype=dtype, param_dtype=dtype))


def _no_training():
    raise BenchError(
        "the lfm2_moe family has no training cell: at 16 bytes a parameter "
        "one chip holds a quarter of the experts of five sparse layers, and "
        "the convolution keeps no state there")


def training_model(config_file: dict, dtype, remat_policy: str):
    _no_training()


def vocab_size(config_file: dict) -> int:
    return config_file["model"]["vocab_size"]


def max_context(config_file: dict) -> int:
    """The longest context the model declares; a cell's traffic mix sizes
    the pool (``max_total``)."""
    return config_file["model"]["max_position_embeddings"]


def reference_shape(config_file: dict) -> dict:
    """What ``reference_lfm2_moe`` takes beside the parameters and ids."""
    m = _checked(config_file)
    return dict(
        heads=m["num_attention_heads"], kv_heads=m["num_key_value_heads"],
        eps=m["norm_eps"], rope_theta=float(m["rope_theta"]),
        top_k=m["num_experts_per_tok"],
        route_eps=TOPK_NORM_EPS,
        route_scale=float(m["routed_scaling_factor"]),
        types=tuple(m["layer_types"]), dense=m["num_dense_layers"])


def reference_logits(config_file: dict):
    """``f(params, input_ids [rows, T]) -> [rows, T, vocab]`` float32, the
    plain reference over the program's own parameter tree; jittable."""
    shape = reference_shape(config_file)
    return lambda params, ids: reference_lfm2_moe.logits(params, ids, shape)


def reference_logits_given(config_file: dict):
    """``f(params, input_ids [rows, T], given [rows, T, sparse layers, k])
    -> (logits, {"inputs", "margin", "differs"})``: the reference with the
    routed sets the PROGRAM chose handed in (``families/mimo_v2.py`` says
    what each is)."""
    shape = reference_shape(config_file)
    return lambda params, ids, given: reference_lfm2_moe.logits(
        params, ids, shape, given, with_layers=True)


def sparse_layers(config_file: dict) -> list:
    """Names of the sparse layers' entries in the parameter tree, in the
    order ``given`` and ``inputs`` count them."""
    m = config_file["model"]
    return [f"layers_{i}_mlp" for i in range(m["num_dense_layers"],
                                             m["num_hidden_layers"])]


def expert_layer_error(config_file: dict, served_config):
    """``f(layer's params, inputs [T, d] float32, valid [T]) -> (error,
    margin)``: the PROGRAM's sparse layer against the reference's experts
    over the same inputs and the program's own routed sets, as
    ``families/mimo_v2.py``'s."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.mimo_v2 import SparseExperts

    shape = reference_shape(config_file)
    layer = SparseExperts(served_config)

    def error(mlp, inputs, valid):
        got, _, chosen = layer.apply({"params": mlp}, inputs[None],
                                     valid[None])
        with jax.default_matmul_precision("highest"):
            _, weights, margin, _ = reference_lfm2_moe.routed(
                inputs, mlp, shape, chosen[0])
            want = reference_lfm2_moe.expert_terms(inputs, mlp, 0, chosen[0],
                                                   weights)
        keep = valid[:, None]
        miss = jnp.sum(jnp.where(keep, got[0] - want, 0.0) ** 2)
        whole = jnp.sum(jnp.where(keep, want, 0.0) ** 2)
        return (jnp.sqrt(miss / jnp.maximum(whole, 1e-30)),
                jnp.max(jnp.where(valid, margin, 0.0)))

    return error


def reference_loss(config_file: dict):
    """``f(params, input_ids) -> (sum of next-token negative
    log-likelihoods, token count)``."""
    import jax
    import jax.numpy as jnp

    logits = reference_logits(config_file)

    def loss(params, ids):
        lg = logits(params, ids)[:, :-1]
        gold = ids[:, 1:]
        nll = jax.nn.logsumexp(lg, axis=-1) - jnp.take_along_axis(
            lg, gold[..., None], axis=-1)[..., 0]
        return nll.sum(), gold.size

    return loss


def train_flops_per_token(config_file: dict, seq_len: int) -> float:
    _no_training()


def attention_shapes(config_file: dict) -> dict:
    """What the kernels' arithmetic asks (``families/mimo_v2.py``):
    ``heads``; ``global``: the attention layers, whose keys and values
    the paged cache holds; ``window``: none; the sparse FFN's shapes under
    ``experts``; and ``state``: the convolution layers and what each keeps
    a decode slot (``rows`` x ``width`` values)."""
    m = _checked(config_file)
    head = m["hidden_size"] // m["num_attention_heads"]
    attn = sum(1 for k in m["layer_types"] if k == "full_attention")
    kv = {"kv_heads": m["num_key_value_heads"], "k_dim": head, "v_dim": head}
    return {"heads": m["num_attention_heads"],
            "global": {"layers": attn, **kv, "window": 0},
            "window": {"layers": 0, **kv, "window": 0},
            "experts": {"layers": m["num_hidden_layers"]
                        - m["num_dense_layers"],
                        "held": m["num_experts"],
                        "hidden": m["hidden_size"],
                        "width": m["moe_intermediate_size"]},
            "state": {"layers": len(m["layer_types"]) - attn,
                      "rows": m["conv_L_cache"] - 1,
                      "width": m["hidden_size"]}}
