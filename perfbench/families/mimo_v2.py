"""The MiMo-V2 family (``"family": "mimo_v2"``): what the harness takes from
a configuration file whose ``model`` holds the keys of a published
``mimo_v2`` ``config.json``. Every function takes the configuration file;
the reference is ``perfbench/reference_mimo_v2.py``.

A CUT file gives the chip's share of a deployment (README.md):
``model.n_routed_experts`` is the experts HELD here and
``published.n_routed_experts`` the router's width, so the share is rank
``held.ep_rank`` of ``published / held`` equal shares; ``model.vocab_size``
is the slice of the vocabulary held, which the traffic draws its ids from
and the logits are over. The file of a model in the driver's catalog
holds ``model``'s keys at its top level too, value for value (the driver's
check reads them there); the family reads ``model`` and refuses a file
whose two copies differ.
"""

from perfbench import reference_mimo_v2
from perfbench.byname import BenchError


def _share(config_file: dict) -> dict:
    m = config_file["model"]
    routed = config_file.get("published", {}).get(
        "n_routed_experts", m["n_routed_experts"])
    if routed % m["n_routed_experts"]:
        raise BenchError(f"{m['n_routed_experts']} experts held do not "
                         f"divide the published {routed}")
    ep_size = routed // m["n_routed_experts"]
    ep_rank = int(config_file.get("held", {}).get("ep_rank", 0))
    return {"n_routed": routed, "ep_size": ep_size, "ep_rank": ep_rank,
            "first_expert": ep_rank * m["n_routed_experts"]}


def _checked(config_file: dict) -> dict:
    """``model``, held to what the program's family implements."""
    m = config_file["model"]
    same = [("swa_head_dim", "head_dim"), ("swa_v_head_dim", "v_head_dim"),
            ("swa_num_attention_heads", "num_attention_heads"),
            ("sliding_window_size", "sliding_window")]
    wrong = [f"{a} != {b}" for a, b in same if m[a] != m[b]]
    fixed = {"hidden_act": "silu", "scoring_func": "sigmoid",
             "topk_method": "noaux_tc", "n_group": 1, "topk_group": 1,
             "norm_topk_prob": True, "attention_bias": False,
             "tie_word_embeddings": False, "n_shared_experts": None,
             "routed_scaling_factor": None}
    wrong += [f"{k} = {m[k]!r}" for k, v in fixed.items() if m[k] != v]
    if (m["rope_scaling"] or {}).get("rope_type", "default") != "default":
        wrong.append("rope_scaling is not the default")
    if wrong:
        raise BenchError(f"the mimo_v2 family does not implement: {wrong}")
    # a committed file repeats ``model``'s keys at its top level, where the
    # driver's check against the catalog reads them: one set of values
    apart = sorted(k for k in m if k in config_file and config_file[k] != m[k])
    if apart:
        raise BenchError(f"top-level {apart} differ from model's")
    return m


def _fields(config_file: dict) -> dict:
    """The program's ``MiMoV2Config`` fields."""
    m, share = _checked(config_file), _share(config_file)
    return dict(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        swa_num_key_value_heads=m["swa_num_key_value_heads"],
        head_dim=m["head_dim"], v_head_dim=m["v_head_dim"],
        hybrid_layer_pattern=tuple(m["hybrid_layer_pattern"]),
        moe_layer_freq=tuple(m["moe_layer_freq"]),
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        n_routed_experts=share["n_routed"], ep_size=share["ep_size"],
        ep_rank=share["ep_rank"],
        num_experts_per_tok=m["num_experts_per_tok"],
        sliding_window=m["sliding_window"], rope_theta=float(m["rope_theta"]),
        swa_rope_theta=float(m["swa_rope_theta"]),
        partial_rotary_factor=m["partial_rotary_factor"],
        attention_value_scale=m["attention_value_scale"],
        layernorm_epsilon=m["layernorm_epsilon"],
        add_swa_attention_sink_bias=m["add_swa_attention_sink_bias"],
        add_full_attention_sink_bias=m["add_full_attention_sink_bias"],
        max_position_embeddings=m["max_position_embeddings"],
        selection_bias_std=float(
            config_file.get("weights", {}).get("selection_bias_std", 0.0)))


def serving_module(config_file: dict, dtype):
    """The module ``init_inference`` serves; its parameters are made in
    the type they are served in (3.4 G of them in float32 would not fit
    beside themselves)."""
    try:
        from deepspeed_tpu.models.mimo_v2 import (MiMoV2Config,
                                                  MiMoV2ForCausalLM)
    except ImportError as e:   # a program older than the family
        raise BenchError(f"this program cannot run the mimo_v2 family: {e}")

    return MiMoV2ForCausalLM(MiMoV2Config(
        **_fields(config_file), dtype=dtype, param_dtype=dtype))


def _no_training():
    raise BenchError(
        "the mimo_v2 family has no training cell: at 16 bytes a parameter "
        "no cut inside the guide's floors trains on one chip")


def training_model(config_file: dict, dtype, remat_policy: str):
    _no_training()


def vocab_size(config_file: dict) -> int:
    """Token ids the traffic draws from: the slice of the vocabulary held."""
    return config_file["model"]["vocab_size"]


def max_context(config_file: dict) -> int:
    """The longest context the model declares; a cell's traffic mix sizes
    the pool (``max_total``)."""
    return config_file["model"]["max_position_embeddings"]


def reference_shape(config_file: dict) -> dict:
    """What ``reference_mimo_v2`` takes beside the parameters and ids."""
    m, share = _checked(config_file), _share(config_file)
    return dict(
        heads=m["num_attention_heads"], kv_heads=m["num_key_value_heads"],
        swa_kv_heads=m["swa_num_key_value_heads"], head_dim=m["head_dim"],
        v_head_dim=m["v_head_dim"], value_scale=m["attention_value_scale"],
        rotary_dim=int(m["head_dim"] * m["partial_rotary_factor"]) // 2 * 2,
        window=m["sliding_window"], rope_theta=float(m["rope_theta"]),
        swa_rope_theta=float(m["swa_rope_theta"]),
        eps=m["layernorm_epsilon"], top_k=m["num_experts_per_tok"],
        first_expert=share["first_expert"],
        pattern=tuple(m["hybrid_layer_pattern"]),
        moe=tuple(m["moe_layer_freq"]))


def reference_logits(config_file: dict):
    """``f(params, input_ids [rows, T]) -> [rows, T, vocab]`` float32, the
    plain reference over the program's own parameter tree, given the same
    share; jittable."""
    shape = reference_shape(config_file)
    return lambda params, ids: reference_mimo_v2.logits(params, ids, shape)


def reference_logits_given(config_file: dict):
    """``f(params, input_ids [rows, T], given [rows, T, sparse layers, k])
    -> (logits, {"inputs", "margin", "differs"})``: the reference with the
    routed sets the PROGRAM chose handed in (negative: the reference's
    own), each sparse layer's float32 input, how far from the reference's
    own choice each handed set lies and where it is another
    (``reference_mimo_v2.logits``)."""
    shape = reference_shape(config_file)
    return lambda params, ids, given: reference_mimo_v2.logits(
        params, ids, shape, given, with_layers=True)


def sparse_layers(config_file: dict) -> list:
    """Names of the sparse layers' entries in the parameter tree, in the
    order ``given`` and ``inputs`` count them."""
    return [f"layers_{i}_mlp" for i, sparse in
            enumerate(config_file["model"]["moe_layer_freq"]) if sparse]


def expert_layer_error(config_file: dict, served_config):
    """``f(layer's params, inputs [T, d] float32, valid [T]) -> (error,
    margin)``: the PROGRAM's sparse layer (the served model's own module,
    at its own types, on its own kernel where a TPU is) against the
    reference's experts over the same inputs and the program's own routed
    sets: root mean square of the difference over that of the reference's
    output, over the valid tokens; and the largest margin of the
    program's sets (``reference_mimo_v2.routed``). The one place where
    ``correct`` sees the experts' arithmetic undiluted: of a token's
    logits the held experts' terms are a small part, on a chip that holds
    one expert in ``ep_size``."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.mimo_v2 import SparseExperts

    shape = reference_shape(config_file)
    layer = SparseExperts(served_config)

    def error(mlp, inputs, valid):
        got, _, chosen = layer.apply({"params": mlp}, inputs[None],
                                     valid[None])
        with jax.default_matmul_precision("highest"):
            _, weights, margin, _ = reference_mimo_v2.routed(
                inputs, mlp, shape["top_k"], chosen[0])
            want = reference_mimo_v2.expert_terms(
                inputs, mlp, shape["first_expert"], chosen[0], weights)
        keep = valid[:, None]
        miss = jnp.sum(jnp.where(keep, got[0] - want, 0.0) ** 2)
        whole = jnp.sum(jnp.where(keep, want, 0.0) ** 2)
        return (jnp.sqrt(miss / jnp.maximum(whole, 1e-30)),
                jnp.max(jnp.where(valid, margin, 0.0)))

    return error


def reference_loss(config_file: dict):
    """``f(params, input_ids) -> (sum of next-token negative
    log-likelihoods over the vocabulary slice, token count)``."""
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
    """What the kernels' arithmetic asks. ``heads``; per KIND of layer
    (``global`` / ``window``) the ``layers`` that keep keys and values in
    the paged cache, their ``kv_heads``, the widths of a key and a value,
    and the ``window`` (0: the whole context); and the sparse FFN's
    shapes under ``experts``: ``layers``, ``held`` here, ``hidden`` and
    ``width`` of one expert's three matrices."""
    m = _checked(config_file)
    kinds = {}
    for kind, flag, kv, window in (
            ("global", 0, m["num_key_value_heads"], 0),
            ("window", 1, m["swa_num_key_value_heads"],
             m["sliding_window"])):
        kinds[kind] = {"layers": sum(1 for k in m["hybrid_layer_pattern"]
                                     if k == flag),
                       "kv_heads": kv, "k_dim": m["head_dim"],
                       "v_dim": m["v_head_dim"], "window": window}
    return {"heads": m["num_attention_heads"], **kinds,
            "experts": {"layers": sum(m["moe_layer_freq"]),
                        "held": m["n_routed_experts"],
                        "hidden": m["hidden_size"],
                        "width": m["moe_intermediate_size"]}}
