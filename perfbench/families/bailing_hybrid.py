"""The Ling-3.0 family (``"family": "bailing_hybrid"``): what the harness
takes from a configuration file whose ``model`` holds the keys of a
published ``bailing_hybrid`` ``config.json``. Every function takes the
configuration file; the reference is ``perfbench/reference_bailing_hybrid.py``.

A CUT file gives the chip's share of a deployment (README.md):
``model.num_experts`` is the experts HELD here and
``published.num_experts`` the router's width, so the share is rank
``held.ep_rank`` of ``published / held`` equal shares (one ROUTING GROUP a
chip where that is ``n_group``); ``model.vocab_size`` is the slice of the
vocabulary held, which the traffic draws its ids from and the logits are
over; the multi-token-prediction layer is cut with the depth (it lies
behind the last layer, on another chip); the two clamp lists are cut to the
layers kept. The file of a model in the driver's catalog holds ``model``'s
keys at its top level too, value for value (the driver's check reads them
there); the family reads ``model`` and refuses a file whose two copies
differ.
"""

from perfbench import reference_bailing_hybrid
from perfbench.byname import BenchError

# keys the family reads nothing of, each at the value that makes it inert
# here (no window, no LoRA on the gate, no nGPT, no norm on values or on the
# up projection, the router's input unscaled, the latent layers' nope part
# kept): copied from the source and held to these values
_INERT = {"use_kda_lora": False, "no_kda_lora": True, "use_nGPT": False,
          "value_norm": False, "up_proj_norm": False, "use_mla_nope": False,
          "scale_router_input": False, "mtp_use_kda": False,
          "use_bias": False, "use_qkv_bias": False}


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
    fixed = {"model_type": "bailing_hybrid", "hidden_act": "silu",
             "scoring_func": "sigmoid", "score_function": "sigmoid",
             "topk_method": "noaux_tc", "norm_topk_prob": True,
             "moe_router_enable_expert_bias": True, "use_qk_norm": True,
             "kda_safe_gate": True, "linear_silu": True,
             "tie_word_embeddings": False, "num_nextn_predict_layers": 0,
             "q_lora_rank": None, "rope_scaling": None,
             "rope_interleave": True, "group_norm_size": 1,
             "num_kv_heads_for_linear_attn": 0,
             "gated_attention_proj_granularity_type": "head_wise", **_INERT}
    wrong = [f"{k} = {m[k]!r}" for k, v in fixed.items() if m[k] != v]
    if not (len(m["expert_swiglu_limit_list"])
            == len(m["share_expert_swiglu_limit_list"]) == n):
        wrong.append(f"the clamp lists do not have {n} entries")
    if m["qk_head_dim"] != m["qk_nope_head_dim"] + m["qk_rope_head_dim"] or (
            m["rotary_dim"] != m["qk_rope_head_dim"]):
        wrong.append("qk_head_dim / rotary_dim do not follow the nope and "
                     "rope widths")
    if m["num_key_value_heads"] != m["num_attention_heads"]:
        wrong.append("num_key_value_heads is not num_attention_heads")
    if wrong:
        raise BenchError(
            f"the bailing_hybrid family does not implement: {wrong}")
    # a committed file repeats ``model``'s keys at its top level, where the
    # driver's check against the catalog reads them: one set of values
    apart = sorted(k for k in m if k in config_file and config_file[k] != m[k])
    if apart:
        raise BenchError(f"top-level {apart} differ from model's")
    return m


def _fields(config_file: dict) -> dict:
    """The program's ``BailingHybridConfig`` fields."""
    m, share = _checked(config_file), _share(config_file)
    return dict(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        head_dim=m["head_dim"], layer_group_size=m["layer_group_size"],
        short_conv_kernel_size=m["short_conv_kernel_size"],
        kda_lower_bound=float(m["kda_lower_bound"]),
        kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        intermediate_size=m["intermediate_size"],
        first_k_dense_replace=m["first_k_dense_replace"],
        moe_intermediate_size=m["moe_intermediate_size"],
        moe_shared_expert_intermediate_size=m[
            "moe_shared_expert_intermediate_size"],
        num_experts=share["n_routed"], ep_size=share["ep_size"],
        ep_rank=share["ep_rank"],
        num_experts_per_tok=m["num_experts_per_tok"],
        n_group=m["n_group"], topk_group=m["topk_group"],
        num_shared_experts=m["num_shared_experts"],
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        expert_swiglu_limit_list=tuple(
            float(x) for x in m["expert_swiglu_limit_list"]),
        share_expert_swiglu_limit_list=tuple(
            float(x) for x in m["share_expert_swiglu_limit_list"]),
        rms_norm_eps=m["rms_norm_eps"], rope_theta=float(m["rope_theta"]),
        max_position_embeddings=m["max_position_embeddings"],
        selection_bias_std=float(
            config_file.get("weights", {}).get("selection_bias_std", 0.0)))


def serving_module(config_file: dict, dtype):
    """The module ``init_inference`` serves; its parameters are made in
    the type they are served in."""
    try:
        from deepspeed_tpu.models.bailing_hybrid import (
            BailingHybridConfig, BailingHybridForCausalLM)
    except ImportError as e:   # a program older than the family
        raise BenchError(f"this program cannot run the bailing_hybrid "
                         f"family: {e}")

    return BailingHybridForCausalLM(BailingHybridConfig(
        **_fields(config_file), dtype=dtype, param_dtype=dtype))


def _no_training():
    raise BenchError(
        "the bailing_hybrid family has no training cell: the delta rule "
        "has no backward here, and at 16 bytes a parameter no cut inside "
        "the guide's floors trains on one chip (2.95 G parameters: 47 GB)")


def training_model(config_file: dict, dtype, remat_policy: str):
    _no_training()


def vocab_size(config_file: dict) -> int:
    """Token ids the traffic draws from: the slice of the vocabulary held."""
    return config_file["model"]["vocab_size"]


def max_context(config_file: dict) -> int:
    """The longest context the model declares; a cell's traffic mix sizes
    the pool (``max_total``)."""
    return config_file["model"]["max_position_embeddings"]


def _kinds(m: dict) -> tuple:
    return tuple("latent" if (i + 1) % m["layer_group_size"] == 0 else "kda"
                 for i in range(m["num_hidden_layers"]))


def reference_shape(config_file: dict) -> dict:
    """What ``reference_bailing_hybrid`` takes beside the parameters and
    ids."""
    m, share = _checked(config_file), _share(config_file)
    n = m["num_hidden_layers"]
    return dict(
        heads=m["num_attention_heads"], head_dim=m["head_dim"],
        lower_bound=float(m["kda_lower_bound"]), eps=m["rms_norm_eps"],
        nope=m["qk_nope_head_dim"], rope=m["qk_rope_head_dim"],
        v_dim=m["v_head_dim"], rank=m["kv_lora_rank"],
        rope_theta=float(m["rope_theta"]), top_k=m["num_experts_per_tok"],
        n_group=m["n_group"], topk_group=m["topk_group"],
        route_scale=float(m["routed_scaling_factor"]),
        first_expert=share["first_expert"], kinds=_kinds(m),
        sparse=tuple(i >= m["first_k_dense_replace"] for i in range(n)),
        limits=tuple(float(x) for x in m["expert_swiglu_limit_list"]),
        shared_limits=tuple(
            float(x) for x in m["share_expert_swiglu_limit_list"]))


def reference_logits(config_file: dict):
    """``f(params, input_ids [rows, T]) -> [rows, T, vocab]`` float32, the
    plain reference over the program's own parameter tree, given the same
    share; jittable."""
    shape = reference_shape(config_file)
    return lambda params, ids: reference_bailing_hybrid.logits(params, ids,
                                                               shape)


def reference_logits_given(config_file: dict):
    """``f(params, input_ids [rows, T], given [rows, T, sparse layers, k],
    stop) -> (logits, {"inputs", "margin", "differs", "states"})``: the
    reference with the routed sets the PROGRAM chose handed in (negative:
    the reference's own), each sparse layer's float32 input, how far from
    the reference's own choice each handed set lies and where it is
    another, and every KDA layer's state after position ``stop - 1``
    (``reference_bailing_hybrid.logits``)."""
    shape = reference_shape(config_file)
    return lambda params, ids, given, stop: reference_bailing_hybrid.logits(
        params, ids, shape, given, with_layers=True, stop=stop)


def balanced_weights(config_file: dict):
    """``f(params, seed) -> params``: the tree with every sparse layer's
    selection bias balanced as ``weights.selection_bias_balance`` says
    (``rows`` x ``tokens`` ids drawn from ``seed`` over the slice held,
    ``steps`` of ``rate``: ``reference_bailing_hybrid.balanced_biases``,
    through the groups), which is what a TRAINED selection bias is: a
    seed's router alone loads its experts and its groups by luck, and the
    experts a decode step touches, its time with them. None where the file
    asks for no balancing."""
    import jax
    import numpy as np

    how = config_file.get("weights", {}).get("selection_bias_balance")
    if not how:
        return None
    shape, names = reference_shape(config_file), sparse_layers(config_file)
    balance = jax.jit(
        lambda params, ids: reference_bailing_hybrid.balanced_biases(
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
    m = config_file["model"]
    return [f"layers_{i}_mlp" for i in range(m["first_k_dense_replace"],
                                             m["num_hidden_layers"])]


def kda_layers(config_file: dict) -> list:
    """Indices of the KDA layers, in the order the state pool and the
    reference's ``states`` count them."""
    return [i for i, kind in enumerate(_kinds(config_file["model"]))
            if kind == "kda"]


def first_kda_recurrence(config_file: dict, served_config):
    """``f(params, input_ids [1, T], stop) -> [heads, key, value]``
    float32: the REFERENCE's recurrence (``reference_bailing_hybrid
    .recurrence``, a token at a time in float32) over what the PROGRAM's
    first layer hands its own recurrence (the served model's own mixer at
    its own types: ``q, k, v``, the log decays and the write strengths of
    ``input_ids``, from its ``intermediates``), the state after position
    ``stop - 1``. Against the slot's stored state what is left is the
    state's own arithmetic (the chunk form, the step kernel, the pool's
    type): the projections' rounding, which is as large as a bfloat16
    state's, is on both sides. The first layer is a KDA layer, read off the
    embedding: no other layer's inputs can be had without the layers
    before it."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models import blocks
    from deepspeed_tpu.models.bailing_hybrid import KdaMixer

    if kda_layers(config_file)[:1] != [0]:
        raise BenchError("the first layer is not a KDA layer")
    cfg = serving_module(config_file, served_config.dtype).config

    def state(params, input_ids, stop):
        x = params["embed_tokens"][input_ids].astype(cfg.dtype)
        u = blocks.RMSNorm(cfg.rms_norm_eps, jnp.float32).apply(
            {"params": params["layers_0_input_layernorm"]},
            x.astype(jnp.float32)).astype(cfg.dtype)
        _, seen = KdaMixer(cfg).apply({"params": params["layers_0_kda"]}, u,
                                      mutable=["intermediates"])
        (inputs,) = seen["intermediates"]["recurrence_inputs"]
        with jax.default_matmul_precision("highest"):
            return reference_bailing_hybrid.recurrence(*inputs, stop)[1][0]

    return state


def expert_layer_error(config_file: dict, served_config):
    """``f(layer's params, inputs [T, d] float32, valid [T], layer) ->
    (error, margin)``: the PROGRAM's sparse layer (the served model's own
    module, at its own types, on its own kernel where a TPU is) against the
    reference's over the same inputs and the program's own routed sets.
    ``error`` is the larger of two, each a share of the root mean square of
    the reference's term: the held ROUTED experts' sum, and the SHARED
    expert's term (apart: in their sum float8 routed experts would hide
    behind a bfloat16 shared expert). ``margin``: how far from the reference
    gate's own choice the program's chosen sets lie (over float32 inputs a
    float32 gate has nothing to flip on). ``layer`` (static): the layer's
    index, whose clamps both sides take."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.bailing_hybrid import SparseExperts

    shape = reference_shape(config_file)

    def error(mlp, inputs, valid, layer):
        got, shared, _, chosen = SparseExperts(served_config, layer).apply(
            {"params": mlp}, inputs[None], valid[None])
        with jax.default_matmul_precision("highest"):
            picked, weights, margin, _ = reference_bailing_hybrid.routed(
                inputs, mlp, shape, chosen[0])
            want = reference_bailing_hybrid.expert_terms(
                inputs, mlp, shape["first_expert"], picked, weights,
                shape["limits"][layer])
            want_shared = reference_bailing_hybrid.swiglu(
                inputs, mlp["shared_experts"], shape["shared_limits"][layer])
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
    """What the kernels' arithmetic asks. ``heads``; ``latent``: the
    ``layers`` that keep a latent row a token, its ``row`` (values counted)
    and ``rank``; ``kda``: the ``layers`` that keep a state a slot, their
    ``heads``, a head's ``key`` and ``value`` widths, the convolution's
    ``taps`` and the ``sub_chunk`` of the chunk form; and the sparse FFN's
    shapes under ``experts``: ``layers``, ``held`` here, ``hidden`` and
    ``width`` of one expert's three matrices (the shared expert is no part
    of the grouped matmul)."""
    m = _checked(config_file)
    kinds = _kinds(m)
    return {"heads": m["num_attention_heads"],
            "latent": {"layers": kinds.count("latent"),
                       "row": m["kv_lora_rank"] + m["qk_rope_head_dim"],
                       "rank": m["kv_lora_rank"]},
            "kda": {"layers": kinds.count("kda"),
                    "heads": m["num_attention_heads"],
                    "key": m["head_dim"], "value": m["head_dim"],
                    "taps": m["short_conv_kernel_size"], "sub_chunk": 16},
            "experts": {"layers": (m["num_hidden_layers"]
                                   - m["first_k_dense_replace"]),
                        "held": m["num_experts"],
                        "hidden": m["hidden_size"],
                        "width": m["moe_intermediate_size"]}}
