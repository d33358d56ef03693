"""The DeepSeek-V3.2 family (``"family": "deepseek_v32"``): what the harness
takes from a configuration file whose ``model`` holds the keys of a
published ``deepseek_v32`` ``config.json``. Every function takes the
configuration file; the reference is ``perfbench/reference_deepseek_v32.py``.

A CUT file gives the chip's share of a deployment (README.md):
``model.n_routed_experts`` is the experts HELD here and
``published.n_routed_experts`` the router's width, so the share is rank
``held.ep_rank`` of ``published / held`` equal shares;
``model.vocab_size`` is the slice of the vocabulary held, which the traffic
draws its ids from and the logits are over; the multi-token-prediction
layer is cut with the depth; ``first_k_dense_replace`` counts the leading
dense layers KEPT. The file of a model in the driver's catalog holds
``model``'s keys at its top level too, value for value; the family reads
``model`` and refuses a file whose two copies differ.
"""

from perfbench import reference_deepseek_v32
from perfbench.byname import BenchError

# keys the family reads nothing of, each at the value that makes it inert
_INERT = {"ep_size": 1, "attention_bias": False, "moe_layer_freq": 1}


def _share(config_file: dict) -> dict:
    m = config_file["model"]
    routed = config_file.get("published", {}).get("n_routed_experts",
                                                  m["n_routed_experts"])
    if routed % m["n_routed_experts"]:
        raise BenchError(f"{m['n_routed_experts']} experts held do not "
                         f"divide the published {routed}")
    ep_rank = int(config_file.get("held", {}).get("ep_rank", 0))
    return {"n_routed": routed, "ep_size": routed // m["n_routed_experts"],
            "ep_rank": ep_rank,
            "first_expert": ep_rank * m["n_routed_experts"]}


def _checked(config_file: dict) -> dict:
    """``model``, held to what the program's family implements."""
    m = config_file["model"]
    fixed = {"model_type": "deepseek_v32", "hidden_act": "silu",
             "scoring_func": "sigmoid", "topk_method": "noaux_tc",
             "norm_topk_prob": True, "tie_word_embeddings": False,
             "num_nextn_predict_layers": 0, **_INERT}
    wrong = [f"{k} = {m.get(k)!r}" for k, v in fixed.items()
             if m.get(k) != v]
    if m["num_key_value_heads"] != m["num_attention_heads"]:
        wrong.append("num_key_value_heads is not num_attention_heads")
    if (m.get("rope_scaling") or {}).get("type") not in (None, "yarn"):
        wrong.append(f"rope_scaling type {m['rope_scaling'].get('type')!r}")
    if not m.get("q_lora_rank"):
        wrong.append("no q_lora_rank: the indexer reads the query's latent")
    if wrong:
        raise BenchError(f"the deepseek_v32 family does not implement: "
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
    """The program's ``DeepseekV32Config`` fields."""
    from deepspeed_tpu.models.deepseek_v2 import YarnScaling

    m, share = _checked(config_file), _share(config_file)
    yarn = _yarn(m)
    return dict(
        vocab_size=m["vocab_size"], hidden_size=m["hidden_size"],
        num_hidden_layers=m["num_hidden_layers"],
        num_attention_heads=m["num_attention_heads"],
        q_lora_rank=m["q_lora_rank"], kv_lora_rank=m["kv_lora_rank"],
        qk_nope_head_dim=m["qk_nope_head_dim"],
        qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"],
        index_n_heads=m["index_n_heads"], index_head_dim=m["index_head_dim"],
        index_topk=m["index_topk"],
        intermediate_size=m["intermediate_size"],
        first_k_dense_replace=m["first_k_dense_replace"],
        moe_intermediate_size=m["moe_intermediate_size"],
        n_routed_experts=share["n_routed"], ep_size=share["ep_size"],
        ep_rank=share["ep_rank"], n_shared_experts=m["n_shared_experts"],
        num_experts_per_tok=m["num_experts_per_tok"], n_group=m["n_group"],
        topk_group=m["topk_group"],
        routed_scaling_factor=float(m["routed_scaling_factor"]),
        rms_norm_eps=m["rms_norm_eps"], rope_theta=float(m["rope_theta"]),
        rope_scaling=None if yarn is None else YarnScaling(**yarn),
        max_position_embeddings=m["max_position_embeddings"],
        selection_bias_std=float(
            config_file.get("weights", {}).get("selection_bias_std", 0.0)))


def serving_module(config_file: dict, dtype):
    """The module ``init_inference`` serves; its parameters are made in
    the type they are served in."""
    try:
        from deepspeed_tpu.models.deepseek_v32 import (
            DeepseekV32Config, DeepseekV32ForCausalLM)
    except ImportError as e:   # a program older than the family
        raise BenchError(f"this program cannot run the deepseek_v32 "
                         f"family: {e}")

    return DeepseekV32ForCausalLM(DeepseekV32Config(
        **_fields(config_file), dtype=dtype, param_dtype=dtype))


def _no_training():
    raise BenchError(
        "the deepseek_v32 family has no training cell: the selection has "
        "no gradient (the indexer trains against the attention's own "
        "distribution), and at 16 bytes a parameter one sparse layer at the "
        "floor of 8 experts is 9.6 GB")


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
    """What ``reference_deepseek_v32`` takes beside the parameters and
    ids."""
    m, share = _checked(config_file), _share(config_file)
    return dict(
        layers=m["num_hidden_layers"], heads=m["num_attention_heads"],
        nope=m["qk_nope_head_dim"], rope=m["qk_rope_head_dim"],
        v_dim=m["v_head_dim"], rank=m["kv_lora_rank"],
        eps=m["rms_norm_eps"], rope_theta=float(m["rope_theta"]),
        yarn=_yarn(m), index_heads=m["index_n_heads"],
        index_dim=m["index_head_dim"], index_topk=m["index_topk"],
        top_k=m["num_experts_per_tok"], n_group=m["n_group"],
        topk_group=m["topk_group"],
        route_scale=float(m["routed_scaling_factor"]),
        dense=m["first_k_dense_replace"],
        first_expert=share["first_expert"])


def reference_logits(config_file: dict):
    """``f(params, input_ids [rows, T]) -> [rows, T, vocab]`` float32, the
    plain reference over the program's own parameter tree, given the same
    share, choosing its own keys and experts; jittable."""
    shape = reference_shape(config_file)
    return lambda params, ids: reference_deepseek_v32.logits(params, ids,
                                                             shape)


def reference_by_layer(config_file: dict):
    """``f(params, ids [T], given [T, sparse layers, k] | None, selected
    [T, layers, words] | None, at [n], keep) -> (logits [n, vocab], [seen
    a layer])``: the same reference ONE LAYER A CALL, the stream handed
    from call to call and given up to the next (a context of 32,768 is a
    stream of 0.94 GB, and a whole pass in one program would hold several
    beside 10.5 GB of served weights and pools). Host arrays in, host
    arrays out; two compiled layer programs a width (the dense and the
    sparse kind)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    ref, shape = reference_deepseek_v32, reference_shape(config_file)
    sparse = ref.sparse_layers(shape)

    def run_layer(x, p, routed, selected, is_sparse, keep):
        with jax.default_matmul_precision("highest"):
            return ref.layer(x, p, shape, is_sparse, routed, selected, keep)

    def ends(params, x, at):
        with jax.default_matmul_precision("highest"):
            return ref.head(params, x, shape, at)

    layer = jax.jit(run_layer, static_argnums=(4, 5), donate_argnums=(0,))
    embed, head = jax.jit(ref.embed), jax.jit(ends)

    def logits(params, ids, given, selected, at, keep=0):
        x = embed(params, jnp.asarray(ids, jnp.int32))
        seen = []
        for i in range(shape["layers"]):
            is_sparse = i in sparse
            routed = chosen = None
            if is_sparse and given is not None:
                routed = jnp.asarray(given[:, sparse.index(i)], jnp.int32)
            if selected is not None:
                chosen = jnp.asarray(selected[:, i], jnp.uint32)
            x, saw = layer(x, ref.layer_params(params, i), routed, chosen,
                           is_sparse, keep if is_sparse else 0)
            # what the check reads of a layer, on the host; its inputs
            # stay on the device for the layer's error
            seen.append({k: (v if k == "inputs" else np.asarray(v))
                         for k, v in saw.items() if k != "selected"
                         or selected is None})
        return np.asarray(head(params, x, jnp.asarray(at, jnp.int32))), seen

    return logits


def balanced_weights(config_file: dict):
    """``f(params, seed) -> params``: the tree with every sparse layer's
    selection bias balanced as ``weights.selection_bias_balance`` says
    (``rows`` x ``tokens`` ids drawn from ``seed`` over the slice held,
    ``steps`` of ``rate``: ``reference_deepseek_v32.balanced_biases``,
    through the groups), which is what a TRAINED selection bias is. None
    where the file asks for no balancing."""
    import jax
    import numpy as np

    how = config_file.get("weights", {}).get("selection_bias_balance")
    if not how:
        return None
    shape, names = reference_shape(config_file), sparse_layers(config_file)
    balance = jax.jit(
        lambda params, ids: reference_deepseek_v32.balanced_biases(
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


def expert_layer_error(config_file: dict, served_config):
    """``f(layer's params, inputs [T, d] float32, valid [T]) -> (error,
    margin)``: the PROGRAM's sparse layer (the served model's own module,
    at its own types, on its own kernel where a TPU is) against the
    reference's over the same inputs and the program's own routed sets.
    ``error``: the larger of two, each a share of the root mean square of
    the reference's term: the held ROUTED experts' sum, and the SHARED
    expert's term (apart: in their sum float8 routed experts would hide
    behind a bfloat16 shared expert). ``margin``: how far from the
    reference gate's own choice the program's chosen sets lie, through the
    groups (over float32 inputs a float32 gate has nothing to flip on)."""
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.deepseek_v32 import SparseExperts
    from perfbench import reference_bailing_hybrid as grouped

    shape = reference_shape(config_file)
    layer = SparseExperts(served_config)

    def error(mlp, inputs, valid):
        got, shared, _, chosen = layer.apply({"params": mlp}, inputs[None],
                                             valid[None])
        with jax.default_matmul_precision("highest"):
            picked, weights, margin, _ = grouped.routed(inputs, mlp, shape,
                                                        chosen[0])
            want = grouped.expert_terms(inputs, mlp, shape["first_expert"],
                                        picked, weights)
            want_shared = grouped.swiglu(inputs, mlp["shared_experts"])
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
    and ``rank``, a head's ``nope`` / ``rope`` / ``v`` widths; ``index``:
    the indexer's ``heads``, ``dim`` (its row a token) and ``topk``; and the
    sparse FFN's shapes under ``experts``."""
    m = _checked(config_file)
    return {"heads": m["num_attention_heads"],
            "latent": {"layers": m["num_hidden_layers"],
                       "rank": m["kv_lora_rank"],
                       "rope": m["qk_rope_head_dim"],
                       "nope": m["qk_nope_head_dim"], "v": m["v_head_dim"],
                       "row": m["kv_lora_rank"] + m["qk_rope_head_dim"]},
            "index": {"layers": m["num_hidden_layers"],
                      "heads": m["index_n_heads"],
                      "dim": m["index_head_dim"], "topk": m["index_topk"]},
            "experts": {"layers": m["num_hidden_layers"]
                        - m["first_k_dense_replace"],
                        "held": m["n_routed_experts"],
                        "hidden": m["hidden_size"],
                        "width": m["moe_intermediate_size"]}}
