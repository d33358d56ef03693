"""The EXAONE-MoE family (``"family": "exaone_moe"``): what the harness
takes from a configuration file whose ``model`` holds the keys of a
published ``exaone_moe`` ``config.json``. Every function takes the
configuration file; the reference is ``perfbench/reference_exaone_moe.py``.

A CUT file gives the chip's share of a deployment (README.md):
``model.num_experts`` is the experts HELD here and
``published.num_experts`` the router's width, so the share is rank
``held.ep_rank`` of ``published / held`` equal shares; ``model.vocab_size``
is the slice of the vocabulary held, which the traffic draws its ids from
and the logits are over; the multi-token-prediction keys are cut with the
depth (the module lies behind the last layer, on another chip). The file of
a model in the driver's catalog holds ``model``'s keys at its top level
too, value for value (the driver's check reads them there); the family
reads ``model`` and refuses a file whose two copies differ.
"""

from perfbench import reference_exaone_moe
from perfbench.byname import BenchError

_WINDOW, _GLOBAL = "sliding_attention", "full_attention"


def _share(config_file: dict) -> dict:
    m = config_file["model"]
    routed = config_file.get("published", {}).get("num_experts",
                                                  m["num_experts"])
    if routed % m["num_experts"]:
        raise BenchError(f"{m['num_experts']} experts held do not divide "
                         f"the published {routed}")
    ep_rank = int(config_file.get("held", {}).get("ep_rank", 0))
    return {"n_routed": routed, "ep_size": routed // m["num_experts"],
            "ep_rank": ep_rank, "first_expert": ep_rank * m["num_experts"]}


def _checked(config_file: dict) -> dict:
    """``model``, held to what the program's family implements."""
    m = config_file["model"]
    n = m["num_hidden_layers"]
    fixed = {"model_type": "exaone_moe", "hidden_act": "silu",
             "scoring_func": "sigmoid", "n_group": 1, "topk_group": 1,
             "norm_topk_prob": True, "tie_word_embeddings": False,
             "num_nextn_predict_layers": 0}
    wrong = [f"{k} = {m[k]!r}" for k, v in fixed.items() if m[k] != v]
    if m["rope_parameters"].get("rope_type", "default") != "default":
        wrong.append("rope_parameters.rope_type is not the default")
    if not (len(m["layer_types"]) == len(m["mlp_layer_types"])
            == len(m["sliding_windows"]) == n):
        wrong.append(f"the per-layer lists do not have {n} entries")
    elif m["sliding_windows"] != [m["sliding_window"] if kind == _WINDOW
                                  else 0 for kind in m["layer_types"]]:
        wrong.append("sliding_windows does not follow layer_types")
    elif m["mlp_layer_types"] != [
            "dense" if i < m["first_k_dense_replace"] else "sparse"
            for i in range(n)]:
        wrong.append("mlp_layer_types does not follow first_k_dense_replace")
    if wrong:
        raise BenchError(f"the exaone_moe family does not implement: {wrong}")
    # a committed file repeats ``model``'s keys at its top level, where the
    # driver's check against the catalog reads them: one set of values
    apart = sorted(k for k in m if k in config_file and config_file[k] != m[k])
    if apart:
        raise BenchError(f"top-level {apart} differ from model's")
    return m


def _fields(config_file: dict) -> dict:
    """The program's ``ExaoneMoeConfig`` fields."""
    m, share = _checked(config_file), _share(config_file)
    return dict(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        num_key_value_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"], layer_types=tuple(m["layer_types"]),
        mlp_layer_types=tuple(m["mlp_layer_types"]),
        intermediate_size=m["intermediate_size"],
        moe_intermediate_size=m["moe_intermediate_size"],
        num_experts=share["n_routed"], ep_size=share["ep_size"],
        ep_rank=share["ep_rank"],
        num_experts_per_tok=m["num_experts_per_tok"],
        num_shared_experts=m["num_shared_experts"],
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        sliding_window=m["sliding_window"],
        rope_theta=float(m["rope_parameters"]["rope_theta"]),
        rms_norm_eps=m["rms_norm_eps"],
        max_position_embeddings=m["max_position_embeddings"],
        selection_bias_std=float(
            config_file.get("weights", {}).get("selection_bias_std", 0.0)))


def serving_module(config_file: dict, dtype):
    """The module ``init_inference`` serves; its parameters are made in
    the type they are served in (3.7 G of them in float32 would not fit
    the chip)."""
    try:
        from deepspeed_tpu.models.exaone_moe import (ExaoneMoeConfig,
                                                     ExaoneMoeForCausalLM)
    except ImportError as e:   # a program older than the family
        raise BenchError(f"this program cannot run the exaone_moe family: "
                         f"{e}")

    return ExaoneMoeForCausalLM(ExaoneMoeConfig(
        **_fields(config_file), dtype=dtype, param_dtype=dtype))


def _no_training():
    raise BenchError(
        "the exaone_moe family has no training cell: at 16 bytes a "
        "parameter no cut inside the guide's floors trains on one chip "
        "(2.50 G parameters at the floors: 40 GB)")


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
    """What ``reference_exaone_moe`` takes beside the parameters and ids."""
    m, share = _checked(config_file), _share(config_file)
    return dict(
        heads=m["num_attention_heads"], kv_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"],
        rope_theta=float(m["rope_parameters"]["rope_theta"]),
        eps=m["rms_norm_eps"], top_k=m["num_experts_per_tok"],
        route_scale=float(m["routed_scaling_factor"]),
        first_expert=share["first_expert"],
        windows=tuple(m["sliding_windows"]),
        sparse=tuple(kind == "sparse" for kind in m["mlp_layer_types"]))


def reference_logits(config_file: dict):
    """``f(params, input_ids [rows, T]) -> [rows, T, vocab]`` float32, the
    plain reference over the program's own parameter tree, given the same
    share; jittable."""
    shape = reference_shape(config_file)
    return lambda params, ids: reference_exaone_moe.logits(params, ids, shape)


def reference_logits_given(config_file: dict):
    """``f(params, input_ids [rows, T], given [rows, T, sparse layers, k])
    -> (logits, {"inputs", "margin", "differs"})``: the reference with the
    routed sets the PROGRAM chose handed in (negative: the reference's
    own), each sparse layer's float32 input, how far from the reference's
    own choice each handed set lies and where it is another
    (``reference_exaone_moe.logits``)."""
    shape = reference_shape(config_file)
    return lambda params, ids, given: reference_exaone_moe.logits(
        params, ids, shape, given, with_layers=True)


def balanced_weights(config_file: dict):
    """``f(params, seed) -> params``: the tree with every sparse layer's
    selection bias balanced as ``weights.selection_bias_balance`` says
    (``rows`` x ``tokens`` ids drawn from ``seed`` over the slice held,
    ``steps`` of ``rate``: ``reference_exaone_moe.balanced_biases``), which
    is what a TRAINED selection bias is: a seed's router alone sends a
    tenth to a sixth of the pairs to an eighth of the experts, and the
    experts a decode step touches, its time with them. None where the file
    asks for no balancing."""
    import jax
    import numpy as np

    how = config_file.get("weights", {}).get("selection_bias_balance")
    if not how:
        return None
    shape, names = reference_shape(config_file), sparse_layers(config_file)
    balance = jax.jit(lambda params, ids: reference_exaone_moe.balanced_biases(
        params, ids, shape, int(how["steps"]), float(how["rate"])))

    def balanced(params, seed):
        ids = np.random.default_rng([int(seed), 17]).integers(
            0, vocab_size(config_file), (int(how["rows"]), int(how["tokens"])))
        biases = balance(params, ids.astype(np.int32))
        # each leaf placed as the one it replaces: the compiled programs
        # see the arguments they were compiled for
        return {**params, **{name: {**params[name], "router_bias":
                                    jax.device_put(bias, params[name][
                                        "router_bias"].sharding)}
                             for name, bias in zip(names, biases)}}

    return balanced


def sparse_layers(config_file: dict) -> list:
    """Names of the sparse layers' entries in the parameter tree, in the
    order ``given`` and ``inputs`` count them."""
    return [f"layers_{i}_mlp" for i, kind in
            enumerate(config_file["model"]["mlp_layer_types"])
            if kind == "sparse"]


def expert_layer_error(config_file: dict, served_config):
    """``f(layer's params, inputs [T, d] float32, valid [T]) -> (error,
    margin)``: the PROGRAM's sparse layer (the served model's own module,
    at its own types, on its own kernel where a TPU is) against the
    reference's over the same inputs and the program's own routed sets.
    ``error`` is the larger of two, each a share of the root mean square of
    the reference's term: the held ROUTED experts' sum, and the SHARED
    expert's term. Apart, because a chip that holds one expert in eight
    adds one weighted expert's term a token beside the unweighted shared
    one: in their sum float8 routed experts would hide behind a bfloat16
    shared expert. ``margin``: how far under the reference gate's own k-th
    selection score the lowest of the program's chosen lies (over float32
    inputs a float32 gate has nothing to flip on)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.exaone_moe import SparseExperts

    shape = reference_shape(config_file)
    layer = SparseExperts(served_config)

    def error(mlp, inputs, valid):
        got, shared, _, chosen = layer.apply({"params": mlp}, inputs[None],
                                             valid[None])
        with jax.default_matmul_precision("highest"):
            picked, weights, margin, _ = reference_exaone_moe.routed(
                inputs, mlp, shape, chosen[0])
            want = reference_exaone_moe.expert_terms(
                inputs, mlp, shape["first_expert"], picked, weights)
            want_shared = reference_exaone_moe.swiglu(
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
    the paged cache, their ``kv_heads``, the widths of a key and a value
    (one head shape in both kinds), and the ``window`` (0: the whole
    context); and the sparse FFN's shapes under ``experts``: ``layers``,
    ``held`` here, ``hidden`` and ``width`` of one expert's three
    matrices (the shared expert is no part of the grouped matmul)."""
    m = _checked(config_file)
    kinds = {}
    for kind, name, window in (("global", _GLOBAL, 0),
                               ("window", _WINDOW, m["sliding_window"])):
        kinds[kind] = {"layers": m["layer_types"].count(name),
                       "kv_heads": m["num_key_value_heads"],
                       "k_dim": m["head_dim"], "v_dim": m["head_dim"],
                       "window": window}
    return {"heads": m["num_attention_heads"], **kinds,
            "experts": {"layers": m["mlp_layer_types"].count("sparse"),
                        "held": m["num_experts"],
                        "hidden": m["hidden_size"],
                        "width": m["moe_intermediate_size"]}}
