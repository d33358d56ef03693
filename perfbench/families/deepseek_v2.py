"""The DeepSeek-V2 family (``"family": "deepseek_v2"``): what the harness
takes from a configuration file whose ``model`` holds the keys of a
published ``deepseek_v2`` ``config.json``. Every function takes the
configuration file; the reference is ``perfbench/reference_deepseek_v2.py``.

A CUT file is cut in depth only (README.md): ``model.num_hidden_layers``
is the source's first layers as they stand, and every expert of every
sparse layer, every head and the whole vocabulary are held. The file of a
model in the driver's catalog holds ``model``'s keys at its top level too,
value for value, ``null``s and the nested ``rope_scaling`` included; the
family refuses a file whose two copies differ. What the source's config
does not fix is the file's ``assumed``.
"""

from perfbench import reference_deepseek_v2
from perfbench.byname import BenchError


def _checked(config_file: dict) -> dict:
    """``model``, held to what the program's family implements."""
    m = config_file["model"]
    fixed = {"model_type": "deepseek_v2", "q_lora_rank": None,
             "attention_bias": False, "hidden_act": "silu",
             "scoring_func": "softmax", "topk_method": "greedy",
             "norm_topk_prob": False, "n_group": 1, "topk_group": 1,
             "moe_layer_freq": 1, "tie_word_embeddings": False}
    wrong = [f"{k} = {m.get(k)!r}" for k, v in fixed.items()
             if m.get(k) != v]
    if m["num_key_value_heads"] != m["num_attention_heads"]:
        wrong.append("num_key_value_heads is not num_attention_heads")
    if (m.get("rope_scaling") or {}).get("type") not in (None, "yarn"):
        wrong.append(f"rope_scaling type {m['rope_scaling'].get('type')!r}")
    if wrong:
        raise BenchError(f"the deepseek_v2 family does not implement: "
                         f"{wrong}")
    apart = sorted(k for k in m if k in config_file and config_file[k] != m[k])
    if apart:
        raise BenchError(f"top-level {apart} differ from model's")
    return m


def _yarn(m: dict):
    scaling = m.get("rope_scaling")
    if not scaling:
        return None
    return {k: scaling[k] for k in (
        "factor", "beta_fast", "beta_slow", "mscale", "mscale_all_dim",
        "original_max_position_embeddings")}


def _fields(config_file: dict) -> dict:
    """The program's ``DeepseekV2Config`` fields."""
    from deepspeed_tpu.models.deepseek_v2 import YarnScaling

    m = _checked(config_file)
    yarn = _yarn(m)
    return dict(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        intermediate_size=m["intermediate_size"],
        first_k_dense_replace=m["first_k_dense_replace"],
        moe_intermediate_size=m["moe_intermediate_size"],
        n_routed_experts=m["n_routed_experts"],
        n_shared_experts=m["n_shared_experts"],
        num_experts_per_tok=m["num_experts_per_tok"],
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        rms_norm_eps=m["rms_norm_eps"], rope_theta=float(m["rope_theta"]),
        rope_scaling=None if yarn is None else YarnScaling(**yarn),
        max_position_embeddings=m["max_position_embeddings"])


def serving_module(config_file: dict, dtype):
    """The module ``init_inference`` serves; its parameters are made in
    the type they are served in."""
    try:
        from deepspeed_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                                      DeepseekV2ForCausalLM)
    except ImportError as e:   # a program older than the family
        raise BenchError(
            f"this program cannot run the deepseek_v2 family: {e}")

    return DeepseekV2ForCausalLM(DeepseekV2Config(
        **_fields(config_file), dtype=dtype, param_dtype=dtype))


def _no_training():
    raise BenchError(
        "the deepseek_v2 family has no training cell: latent attention "
        "exists to shrink the cache a decode step reads (in training it is "
        "three more matmuls), and at 16 bytes a parameter one chip holds "
        "an eighth of one sparse layer's experts")


def training_model(config_file: dict, dtype, remat_policy: str):
    _no_training()


def vocab_size(config_file: dict) -> int:
    return config_file["model"]["vocab_size"]


def max_context(config_file: dict) -> int:
    """The longest context the model declares; a cell's traffic mix sizes
    the pool (``max_total``)."""
    return config_file["model"]["max_position_embeddings"]


def reference_shape(config_file: dict) -> dict:
    """What ``reference_deepseek_v2`` takes beside the parameters and
    ids."""
    m = _checked(config_file)
    return dict(
        layers=m["num_hidden_layers"], heads=m["num_attention_heads"],
        nope=m["qk_nope_head_dim"], rope=m["qk_rope_head_dim"],
        v_dim=m["v_head_dim"], rank=m["kv_lora_rank"],
        eps=m["rms_norm_eps"], rope_theta=float(m["rope_theta"]),
        yarn=_yarn(m), top_k=m["num_experts_per_tok"],
        route_scale=float(m["routed_scaling_factor"]),
        dense=m["first_k_dense_replace"], first_expert=0)


def reference_logits(config_file: dict):
    """``f(params, input_ids [rows, T]) -> [rows, T, vocab]`` float32, the
    plain reference over the program's own parameter tree; jittable."""
    shape = reference_shape(config_file)
    return lambda params, ids: reference_deepseek_v2.logits(
        params, ids, shape)


def reference_logits_given(config_file: dict):
    """``f(params, input_ids [rows, T], given [rows, T, sparse layers, k],
    at [n]) -> (logits [rows, n, vocab], {"inputs", "margin",
    "differs"})``: the reference with the routed sets the PROGRAM chose
    handed in (``families/mimo_v2.py`` says what each is), its head taken
    at the positions ``at`` alone: a context of 16,384 over a vocabulary
    of 102,400 is 6.7 GB of float32 logits, which no chip holds beside the
    served weights and the pool."""
    shape = reference_shape(config_file)
    return lambda params, ids, given, at: reference_deepseek_v2.logits(
        params, ids, shape, given, with_layers=True, at=at)


def sparse_layers(config_file: dict) -> list:
    """Names of the sparse layers' entries in the parameter tree, in the
    order ``given`` and ``inputs`` count them."""
    m = config_file["model"]
    return [f"layers_{i}_mlp" for i in range(m["first_k_dense_replace"],
                                             m["num_hidden_layers"])]


def expert_layer_error(config_file: dict, served_config):
    """``f(layer's params, inputs [T, d] float32, valid [T]) -> (error,
    margin)``: the PROGRAM's sparse layer against the reference's over the
    same inputs and the program's own routed sets. ``error`` is the larger
    of two, each a share of the root mean square of the reference's term:
    the ROUTED experts' sum, and the SHARED experts' term. Apart, because
    the softmax's chosen weights add up to a third or so and the shared
    experts' term is unweighted: in the layer's whole output float8 routed
    experts would hide behind bfloat16 shared ones. ``margin``: how far
    under the reference gate's own k-th probability the lowest of the
    program's chosen lies (over float32 inputs a float32 gate has nothing
    to flip on)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.deepseek_v2 import SparseExperts

    shape = reference_shape(config_file)
    layer = SparseExperts(served_config)

    def error(mlp, inputs, valid):
        got, shared, _, chosen = layer.apply({"params": mlp}, inputs[None],
                                             valid[None])
        with jax.default_matmul_precision("highest"):
            picked, weights, margin, _ = reference_deepseek_v2.routed(
                inputs, mlp, shape, chosen[0])
            want = reference_deepseek_v2.expert_terms(
                inputs, mlp, shape["first_expert"], picked, weights)
            want_shared = reference_deepseek_v2.swiglu(
                inputs, mlp["shared_experts"])
        keep = valid[:, None]

        def apart(a, b):
            miss = jnp.sum(jnp.where(keep, a - b, 0.0) ** 2)
            whole = jnp.sum(jnp.where(keep, b, 0.0) ** 2)
            return jnp.sqrt(miss / jnp.maximum(whole, 1e-30))

        return (jnp.maximum(apart(got[0], want),
                            apart(shared[0], want_shared)),
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
    """What the kernels' arithmetic asks: ``heads``; ``latent``: the
    layers, and what a token keeps in each (``row`` values: the compressed
    key/value ``rank`` and the rope key, shared by all heads); the sparse
    FFN's shapes under ``experts``. No ``global`` or ``window`` layers: no
    row of this family's pool is keys and values by heads."""
    m = _checked(config_file)
    return {"heads": m["num_attention_heads"],
            "latent": {"layers": m["num_hidden_layers"],
                       "rank": m["kv_lora_rank"],
                       "rope": m["qk_rope_head_dim"],
                       "row": m["kv_lora_rank"] + m["qk_rope_head_dim"]},
            "experts": {"layers": m["num_hidden_layers"]
                        - m["first_k_dense_replace"],
                        "held": m["n_routed_experts"],
                        "hidden": m["hidden_size"],
                        "width": m["moe_intermediate_size"]}}
