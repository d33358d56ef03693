"""Tensor-parallel injection policies.

Capability parity with the reference ``deepspeed/module_inject``: where the
reference *rewrites modules* — ``ReplaceWithTensorSlicing`` physically slices
weights across ranks (``module_inject/replace_module.py:20``) and swaps
``nn.Linear`` for ``LinearLayer``/``LinearAllreduce`` (``module_inject/
layers.py:9,25``) guided by per-architecture ``replace_policy.py`` classes —
the TPU-native design only *annotates*: a policy maps parameter paths to
``PartitionSpec``s over the ``tp`` mesh axis, and GSPMD inserts the
column/row-parallel collectives (the row-parallel output ``all_reduce``
becomes an XLA ``psum`` chosen by the partitioner). The explicit
injected form — shard_map bodies that OWN their collective, which is
what lets the int8 tier ride the tp wire — lives in ``layers.py``.

Roles:
- ``column``: output-dim sharded (reference ``LinearLayer``) — no collective
  on forward; activations become model-sharded.
- ``row``: input-dim sharded (reference ``LinearAllreduce``) — GSPMD emits the
  psum that ``LinearAllreduce.forward`` issues explicitly.
- ``vocab``: embedding tables — shard the largest (vocab) dim; lookups become
  masked-gather + psum.
- ``replicate``: everything else (layernorms, small biases).

Policies match *path segments* (module names along the flax param path), so
the same rules apply whether layers are scanned (leading ``layers`` dim) or
unrolled.
"""

import re
from typing import Dict, Optional, Sequence, Tuple

from jax.sharding import PartitionSpec as P

from deepspeed_tpu.parallel.topology import AXIS_TP

COLUMN = "column"
ROW = "row"
VOCAB = "vocab"
REPLICATE = "replicate"

# --- parameter families (the SpecLayout vocabulary) -------------------
# Every parameter belongs to exactly one family; the family determines
# its canonical tp-axis PartitionSpec (runtime/zero/partition.SpecLayout)
# in BOTH training and serving:
#   embedding -> vocab dim over tp;  attn_qkv / mlp_in -> output dim
#   (column-parallel);  attn_proj / mlp_out -> input dim (row-parallel,
#   GSPMD places the tp all-reduce);  norm / other -> replicated.
FAMILY_EMBED = "embedding"
FAMILY_ATTN_QKV = "attn_qkv"
FAMILY_ATTN_PROJ = "attn_proj"
FAMILY_MLP_IN = "mlp_in"
FAMILY_MLP_OUT = "mlp_out"
FAMILY_NORM = "norm"
FAMILY_OTHER = "other"

# path segments that mark the attention submodule (splits the column/row
# roles into their attn vs MLP families)
_ATTN_PARENTS = {"attn", "attention", "self_attn", "self_attention",
                 "crossattention", "cross_attn"}
_NORM_SEGMENTS = {"ln", "ln_1", "ln_2", "ln_f", "emb_ln", "norm",
                  "layernorm", "layer_norm", "input_layernorm",
                  "post_attention_layernorm", "final_layer_norm",
                  "ln_attn", "ln_mlp"}


def family_for(path: str, shape: Tuple[int, ...], policy) -> str:
    """Parameter family of ``path`` under ``policy`` (docstring above).
    Purely descriptive — ``TPPolicy.spec_for`` stays the spec authority;
    this names WHY a param got its spec (docs, manifest, tests)."""
    segments = path.split("/")
    if _NORM_SEGMENTS & set(segments):
        return FAMILY_NORM
    role = policy.role_for(path)
    if role == VOCAB:
        return FAMILY_EMBED
    in_attn = bool(_ATTN_PARENTS & set(segments))
    if role == COLUMN:
        return FAMILY_ATTN_QKV if in_attn else FAMILY_MLP_IN
    if role == ROW:
        return FAMILY_ATTN_PROJ if in_attn else FAMILY_MLP_OUT
    return FAMILY_OTHER


class TPPolicy:
    """Maps parameter paths to TP roles.

    ``rules``: ordered ``(segment_name, role)`` pairs; a parameter whose path
    contains ``segment_name`` as a full segment gets that role (first match
    wins). The analog of one reference ``replace_policy.py`` class, expressed
    as sharding rules instead of weight-slicing instructions.
    """

    def __init__(self, name: str, rules: Sequence[Tuple[str, str]]):
        self.name = name
        self.rules = list(rules)

    def role_for(self, path: str) -> str:
        segments = set(path.split("/"))
        for seg, role in self.rules:
            if seg in segments:
                return role
        return REPLICATE

    def spec_for(self, path: str, shape: Tuple[int, ...], tp_size: int,
                 axis: str = AXIS_TP) -> Optional[P]:
        """PartitionSpec for one param, or None (replicated)."""
        role = self.role_for(path)
        if role == REPLICATE or tp_size <= 1 or not shape:
            return None
        leaf = path.rsplit("/", 1)[-1]
        is_bias = leaf in ("bias", "b") or len(shape) == 1
        if role == COLUMN:
            dim = len(shape) - 1  # output dim (bias included: its only dim)
        elif role == ROW:
            if is_bias:
                return None  # row-parallel bias applies after the psum
            dim = len(shape) - 2
        elif role == VOCAB:
            dim = max(range(len(shape)), key=lambda i: shape[i])
        else:
            raise ValueError(f"unknown TP role {role!r}")
        if dim < 0 or shape[dim] % tp_size != 0:
            return None
        entries = [None] * len(shape)
        entries[dim] = axis
        return P(*entries)


# ----------------------------------------------------------------------
# Built-in policies (reference replace_policy.py arch classes)

_QKV_UP = [  # column-parallel: qkv projections and MLP up-projections
    "c_attn", "q_proj", "k_proj", "v_proj", "qkv_proj", "query", "key",
    "value", "query_key_value", "c_fc", "fc1", "fc_in", "gate_proj",
    "up_proj", "dense_h_to_4h", "wi", "wi_0", "wi_1", "in_proj", "w1", "w3",
]
_OUT_DOWN = [  # row-parallel: attention output and MLP down-projections
    "o_proj", "out_proj", "c_proj", "fc2", "fc_out", "down_proj",
    "dense_4h_to_h", "wo", "dense", "w2",
]
_EMBED = ["wte", "embed_tokens", "word_embeddings", "embedding", "lm_head",
          "shared", "embed_out"]

AUTO_POLICY = TPPolicy(
    "auto",
    [(s, ROW) for s in _OUT_DOWN]
    + [(s, COLUMN) for s in _QKV_UP]
    + [(s, VOCAB) for s in _EMBED])

GPT2_POLICY = TPPolicy(
    "gpt2",
    [("c_proj", ROW), ("c_attn", COLUMN), ("c_fc", COLUMN), ("wte", VOCAB),
     # untied heads of canonical-decoder archs (GPT-J/NeoX); GPT-2 itself
     # has no lm_head param, so the rule is inert there
     ("lm_head", VOCAB)])

# Per-architecture policy zoo (reference replace_policy.py arch classes,
# module_inject/replace_policy.py:174-712 — BERT/CLIP/GPT-Neo/GPT-J/
# Megatron/GPT2/BLOOM/GPT-NeoX/OPT): each names the arch's column-parallel
# inputs (QKV + MLP up), row-parallel outputs (attn out + MLP down), and
# vocab-sharded embeddings. The reference slices weights per these maps;
# here they become PartitionSpec rules GSPMD executes.
LLAMA_POLICY = TPPolicy(
    "llama",
    [("o_proj", ROW), ("down_proj", ROW),
     ("q_proj", COLUMN), ("k_proj", COLUMN), ("v_proj", COLUMN),
     ("gate_proj", COLUMN), ("up_proj", COLUMN),
     ("embed_tokens", VOCAB), ("lm_head", VOCAB)])

OPT_POLICY = TPPolicy(
    "opt",
    [("out_proj", ROW), ("fc2", ROW),
     ("q_proj", COLUMN), ("k_proj", COLUMN), ("v_proj", COLUMN),
     ("fc1", COLUMN), ("embed_tokens", VOCAB), ("lm_head", VOCAB)])

BLOOM_POLICY = TPPolicy(
    "bloom",
    [("dense", ROW), ("dense_4h_to_h", ROW),
     ("query_key_value", COLUMN), ("dense_h_to_4h", COLUMN),
     ("word_embeddings", VOCAB), ("lm_head", VOCAB)])

GPTJ_POLICY = TPPolicy(
    "gptj",
    [("out_proj", ROW), ("fc_out", ROW),
     ("q_proj", COLUMN), ("k_proj", COLUMN), ("v_proj", COLUMN),
     ("fc_in", COLUMN), ("wte", VOCAB), ("lm_head", VOCAB)])

GPT_NEOX_POLICY = TPPolicy(
    "gpt-neox",
    [("dense", ROW), ("dense_4h_to_h", ROW),
     ("query_key_value", COLUMN), ("dense_h_to_4h", COLUMN),
     ("embed_in", VOCAB), ("embed_out", VOCAB)])

BERT_POLICY = TPPolicy(
    "bert",
    [("output_dense", ROW),  # attention output projection (models/bert.py)
     ("output", ROW),        # FFN down-projection
     ("query", COLUMN), ("key", COLUMN), ("value", COLUMN),
     ("intermediate", COLUMN), ("word_embeddings", VOCAB)])

CLIP_POLICY = TPPolicy(
    "clip",
    # both CLIP towers share the pre-LN encoder layer (reference
    # HFCLIPLayerPolicy, replace_policy.py:236): separate q/k/v + fc1 are
    # column-parallel, out_proj + fc2 row-parallel; the token table
    # shards over vocab
    [("out_proj", ROW), ("fc2", ROW),
     ("q_proj", COLUMN), ("k_proj", COLUMN), ("v_proj", COLUMN),
     ("fc1", COLUMN), ("token_embedding", VOCAB)])

_POLICIES: Dict[str, TPPolicy] = {
    "auto": AUTO_POLICY, "gpt2": GPT2_POLICY, "llama": LLAMA_POLICY,
    "opt": OPT_POLICY, "bloom": BLOOM_POLICY, "gptj": GPTJ_POLICY,
    "gpt-neox": GPT_NEOX_POLICY, "bert": BERT_POLICY, "clip": CLIP_POLICY,
}


def register_tp_policy(policy: TPPolicy):
    """User plug point (reference ``injection_policy`` kwarg)."""
    _POLICIES[policy.name] = policy


def get_tp_policy(name: str = "auto") -> TPPolicy:
    if isinstance(name, TPPolicy):
        return name
    if name not in _POLICIES:
        raise ValueError(f"unknown TP policy {name!r}; have {sorted(_POLICIES)}")
    return _POLICIES[name]


def specs_from_policy(policy: TPPolicy, params_abstract, mesh,
                      axis: str = AXIS_TP):
    """Pytree of base PartitionSpecs (or None) for each param.

    Feed as ``param_specs`` to ``build_zero_shardings`` — ZeRO layers its
    data-axis sharding on the dims TP left alone.
    """
    import jax

    from deepspeed_tpu.parallel.topology import resolve_axis_name
    from deepspeed_tpu.utils.pytree import flatten_with_path_strings

    axis = resolve_axis_name(mesh, axis)  # legacy "model"-named meshes
    tp_size = int(mesh.shape.get(axis, 1))
    flat, treedef = flatten_with_path_strings(params_abstract)
    specs = [policy.spec_for(path, tuple(leaf.shape), tp_size, axis)
             for path, leaf in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def decode_cache_specs(cache_abstract, mesh, axis: str = AXIS_TP,
                       heads: int = 0):
    """PartitionSpecs for a decode KV cache under tensor parallelism.

    The cache is the decode working set the TP layout must keep sharded.
    ``cached_key``/``cached_value`` leaves (the append cache) carry the
    layout ``[..., positions, heads, head_dim]`` and shard their head
    axis (-2). The serving block pools keep ONE shape, ``[layers, blocks,
    block_size, lanes]`` (``ops/decode_attention.py``): ``key_pool`` /
    ``value_pool`` rows are ``heads * head_dim`` lanes, so sharding the
    LANE axis over ``axis`` gives each tp shard ``heads / tp`` contiguous
    heads of every pool row — the heads the QKV column-split distributed,
    exactly like the reference splits its inference KV workspace per TP
    rank (``inference_context.h`` workspace carved per ``mp_size``): each
    tp shard owns a per-shard KV pool. A pool row does not say how many
    heads it holds, so the caller passes ``heads`` (the model's
    ``n_head``); pools replicate without it. The int8 ``key_scale`` /
    ``value_scale`` side pools (a lane a head, padded to whole registers;
    a sixteenth of the int8 rows' bytes at head size 64) replicate: the
    kernel finds a shard's heads in the whole row.
    Scalars/per-row bookkeeping (``cache_index``, ``position``,
    ``pad_len``) replicate, as do head-indivisible caches, and so does a
    ``latent_pool`` (``models/deepseek_v2.py``): its row is ONE latent a
    token shared by every head, with no lanes a head to split (the
    engine refuses that model at ``tp_size`` > 1 until a test has met it).
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from deepspeed_tpu.ops.decode_attention import POOL_LANE_AXIS
    from deepspeed_tpu.parallel.topology import resolve_axis_name
    from deepspeed_tpu.utils.pytree import flatten_with_path_strings

    axis = resolve_axis_name(mesh, axis)  # legacy "model"-named meshes
    tp = int(mesh.shape.get(axis, 1))
    flat, treedef = flatten_with_path_strings(cache_abstract)

    def spec(path, leaf):
        leaf_name = path.rsplit("/", 1)[-1]
        if leaf_name in ("cached_key", "cached_value"):
            head_axis, n = len(leaf.shape) - 2, leaf.shape[-2]
        elif leaf_name in ("key_pool", "value_pool"):
            head_axis, n = POOL_LANE_AXIS, heads
        else:
            return P()
        if tp > 1 and n and n % tp == 0:
            parts = [None] * len(leaf.shape)
            parts[head_axis] = axis
            return P(*parts)
        return P()

    return jax.tree_util.tree_unflatten(
        treedef, [NamedSharding(mesh, spec(p, l)) for p, l in flat])


def shard_params_with_policy(params, policy, mesh, axis: str = AXIS_TP):
    """Place a param pytree per the policy's TP specs.

    The one sharding entry point serving engines share (InferenceEngine
    and CLIPServingEngine): ``(sharded_params, shardings)`` with
    unmatched leaves replicated. ``policy`` may be a name or a TPPolicy.
    """
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    abstract = jax.eval_shape(lambda p: p, params)
    specs = specs_from_policy(get_tp_policy(policy), abstract, mesh, axis)
    shardings = jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s if s is not None else P()),
        specs, is_leaf=lambda s: s is None or isinstance(s, P))
    # a leaf that already lies where its sharding wants it (a tree made on
    # the device, one chip) is taken as it lies: the same buffers under the
    # mesh's sharding, so that the programs see one argument signature. Only
    # the others are placed (a copy): weights of more than half a chip's
    # memory would not fit beside a second copy of themselves
    leaves, treedef = jax.tree_util.tree_flatten(params)
    wanted = treedef.flatten_up_to(shardings)
    placed = [_as_it_lies(x, s) for x, s in zip(leaves, wanted)]
    rest = [i for i, x in enumerate(placed) if x is None]
    if rest:
        moved = jax.jit(lambda p: p, out_shardings=[wanted[i] for i in rest])(
            [leaves[i] for i in rest])
        for i, x in zip(rest, moved):
            placed[i] = x
    return jax.tree_util.tree_unflatten(treedef, placed), shardings


def _as_it_lies(x, sharding):
    """``x`` under ``sharding`` without a copy, where its buffers already
    lie as ``sharding`` asks (the same devices, the same shards); else
    None."""
    import jax

    if not (isinstance(x, jax.Array) and x.is_fully_addressable
            and not x.is_deleted()
            and x.sharding.is_equivalent_to(sharding, x.ndim)):
        return None
    return jax.make_array_from_single_device_arrays(
        x.shape, sharding, [shard.data for shard in x.addressable_shards])
