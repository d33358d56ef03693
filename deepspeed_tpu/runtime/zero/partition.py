"""ZeRO stages as GSPMD sharding policies.

The reference implements ZeRO with explicit bookkeeping: flat-buffer
round-robin partitions (``runtime/zero/stage_1_and_2.py:609``), grad-hook IPG
buckets (``:836-942``), and for stage 3 per-param ``ds_tensor`` shards with
gather/release hooks (``runtime/zero/partition_parameters.py:1042``,
``partitioned_param_coordinator.py:239``). On TPU all of that collapses into
*where each array lives on the mesh*:

- **stage 1**: optimizer state (m/v) sharded over the data axes; params
  replicated. XLA's weight-update sharding: grads reduce-scatter into the
  owner shard, updated weights all-gather back — the reference's
  ``allgather_bucket`` loop (``stage_1_and_2.py:1821``) becomes an output
  sharding spec.
- **stage 2**: same program — gradients never materialize replicated because
  the only consumer (the sharded update) needs 1/N of them; XLA's scheduler
  plays the role of the IPG overlap stream.
- **stage 3**: params themselves sharded. Two programs, chosen by what
  the engine observes (``engine._zero3_plan``), never by an option:

  * a model written against the seam (:func:`gather_at_use`; it declares
    its use sites with ``zero3_use_sites()``, as the scanned GPT-2 does)
    gets the EXPLICIT program: loss and gradients run under ``shard_map``
    over the live ZeRO axes (``data``, ``fsdp``). Inside the layer scan
    one layer's slice of every sharded leaf is cast to the compute dtype
    ON THE SHARD and all-gathered (bf16 on the wire; in the forward one
    layer ahead of its use; backward gathers again, so no gathered weight
    is ever a residual); its gradient is cast up to float32, summed and scattered,
    so the sum across chips is float32 and each chip ends holding its own
    shard: by a ring of float32 ``ppermute``s and adds
    (:func:`ring_reduce_scatter`; asynchronous on the chip, so the rest of
    the layer's backward runs beside them) where the scatter group is 2 to
    8 chips, by the backend's reduce-scatter otherwise
    (:func:`scatter_form`). The tables outside the scan (``wte``, ``wpe``,
    an untied head) are gathered once for all their uses, bf16 on the
    wire, float32 values; their gradients are summed the same way.
    Activations are each chip's own rows from embedding to loss:
    no collective ever moves one. The loss is the mean over chips of each
    chip's batch mean, as the reference's is.
  * any other model (a user's module, the unrolled ``LoopBlocks``,
    ``models/llama.py``), and the regimes that own the loss's program
    (tp / expert / seq / pipe axes, compression, host offload,
    comm-quantization), keep the GSPMD program: the sharded parameters go
    into ``value_and_grad`` and the partitioner places whatever
    collectives it derives, which for a feature-sharded weight and a
    batch-sharded activation are all-to-alls of the ACTIVATIONS (PERF.md,
    PR 25).

Sharding rule: shard the largest dimension divisible by the axis size; params
smaller than ``param_persistence_threshold`` stay replicated (mirrors
``stage3_param_persistence_threshold``). Under a use site the unit is the
one that is gathered: persistence is judged on ONE LAYER's slice of a
stacked leaf (a layer's biases and norms are persistent, whole on every
chip), the scanned dim is never split, and the slice's FIRST divisible
dim is (a gather along it is a concatenation, its transpose one
reduce-scatter). Optimizer state and gradients take the same dims.

Since the 3-axis mesh (``data x fsdp x tp``, GSPMD arXiv:2105.04663) the
one authority over *which axis shards what* is :class:`SpecLayout`:
canonical PartitionSpecs per parameter family (embeddings, attention
QKV/proj, MLP in/out, norms) on the ``tp`` axis, ZeRO layering over
``data x fsdp x expert``, and batch arrays over ``data x expert`` ONLY —
``fsdp``/``tp`` never shard the batch dimension. Training shardings,
the topology manifest, the AOT fingerprint and the serving engines all
consume the same layout, so the partitioning of a tensor family cannot
diverge between training and inference.
"""

from typing import Dict, Optional, Sequence, Tuple

import jax
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from deepspeed_tpu.parallel.topology import (AXIS_DATA, AXIS_EXPERT,
                                             AXIS_FSDP, AXIS_SEQ, AXIS_TP,
                                             axis_spec_entry)
from deepspeed_tpu.utils.pytree import key_entry_str

# ZeRO partitions optimizer state / ZeRO-3 params over these axes (the
# flattened product is the reference's "partition count"); the batch only
# ever shards over BATCH_AXES — fsdp buys param/opt-state memory headroom
# without forcing more data parallelism, tp never touches the batch.
ZERO_AXES = (AXIS_DATA, AXIS_FSDP, AXIS_EXPERT)
BATCH_AXES = (AXIS_DATA, AXIS_EXPERT)


def hierarchical_param_axes(zero_axes: Sequence[str] = ZERO_AXES
                            ) -> Tuple[str, ...]:
    """The ZeRO axes a *hierarchical* (ZeRO++ hpZ, arXiv:2306.10209) param
    shard spans: everything but ``data`` — i.e. the shard lives inside one
    data replica, so the per-use all-gather crosses only the fsdp/expert
    wire instead of the full data x fsdp group. Optimizer and gradient
    state keep the full ``zero_axes`` partition (the once-per-step update
    path), only the per-layer-per-tick param fetch shrinks."""
    return tuple(a for a in zero_axes if a != AXIS_DATA)


def _shardable_dim(shape: Tuple[int, ...], axis_size: int,
                   taken: Sequence[Optional[str]],
                   stacked: int = 0, leading: bool = False) -> Optional[int]:
    """Largest dim divisible by axis_size and not already sharded, or
    with ``leading`` the FIRST such dim; the ``stacked`` leading (scanned)
    dims are never a candidate."""
    best, best_size = None, 0
    for i, d in enumerate(shape):
        if i >= stacked and taken[i] is None and d % axis_size == 0 \
                and d >= axis_size and d > best_size:
            if leading:
                return i
            best, best_size = i, d
    return best


def zero_partition_spec(shape: Tuple[int, ...],
                        mesh: Mesh,
                        data_axes: Optional[Sequence[str]] = None,
                        base_spec: Optional[P] = None,
                        persistence_threshold: int = 0,
                        stacked: int = 0, leading: bool = False) -> P:
    """PartitionSpec sharding ``shape`` over the (flattened) data axes,
    layered on top of ``base_spec`` (TP/expert specs from the model).

    Returns ``base_spec`` unchanged if the array is too small (persistence
    threshold) or no dim divides evenly. ``stacked`` leading dims are a
    layer scan's: the unit that is gathered at a use is ``shape[stacked:]``,
    so persistence is judged on that and the scanned dims stay whole.
    ``leading`` shards the unit's FIRST divisible dim, not its largest: an
    all-gather along it is a concatenation and its reduce-scatter is one
    collective, where the chip's compiler turns a scatter along a minor
    dim into an all-reduce of the whole gradient and a slice.
    """
    if data_axes is None:
        data_axes = ZERO_AXES
    entries = list(base_spec) if base_spec is not None else []
    entries += [None] * (len(shape) - len(entries))
    used = {a for e in entries for a in (e if isinstance(e, tuple) else (e,)) if a}
    # a mesh axis may appear at most once in a spec: e.g. expert params carry
    # "expert" in their base spec, so ZeRO shards them over "data" only
    data_axes = [a for a in data_axes if mesh.shape.get(a, 1) > 1 and a not in used]
    if not data_axes:
        return base_spec if base_spec is not None else P()
    axis_size = int(np.prod([mesh.shape[a] for a in data_axes]))
    if int(np.prod(shape[stacked:])) < max(persistence_threshold, axis_size):
        return P(*entries) if base_spec is not None else P()
    dim = _shardable_dim(shape, axis_size, entries, stacked, leading)
    if dim is None:
        return P(*entries) if base_spec is not None else P()
    group = tuple(data_axes) if len(data_axes) > 1 else data_axes[0]
    entries[dim] = group
    return P(*entries)


def build_zero_shardings(params_shapes,
                         mesh: Mesh,
                         stage: int,
                         param_specs=None,
                         persistence_threshold: int = 0,
                         hierarchical: bool = False,
                         sites: Optional[Dict[str, int]] = None):
    """Shardings for (params, optimizer state) given a ZeRO stage.

    ``params_shapes``: pytree of ``jax.ShapeDtypeStruct`` (or arrays).
    ``param_specs``: optional pytree of base PartitionSpecs (TP rules).
    ``hierarchical``: hpZ — stage-3 *params* shard over
    :func:`hierarchical_param_axes` only (inside a data replica);
    optimizer state keeps the full :data:`ZERO_AXES` partition.
    ``sites``: the model's use sites (:func:`use_sites_of`), path prefix ->
    leading scanned dims; a leaf under one is partitioned by its per-layer
    slice (:func:`zero_partition_spec`, ``stacked``).
    Returns ``(param_shardings, opt_shardings)`` pytrees of NamedSharding.
    """

    def base_spec_of(leaf_spec):
        return leaf_spec if leaf_spec is not None else None

    if param_specs is None:
        param_specs = jax.tree_util.tree_map(lambda _: None, params_shapes)

    param_axes = hierarchical_param_axes() if hierarchical else ZERO_AXES

    def param_sharding(path, leaf, spec):
        base = base_spec_of(spec)
        if stage >= 3:
            site, stacked = site_of(sites, path)
            s = zero_partition_spec(leaf.shape, mesh,
                                    data_axes=param_axes,
                                    base_spec=base,
                                    persistence_threshold=persistence_threshold,
                                    stacked=stacked, leading=site is not None)
        else:
            s = base if base is not None else P()
        return NamedSharding(mesh, s)

    def opt_sharding(path, leaf, spec):
        base = base_spec_of(spec)
        if stage >= 1:
            # the same dims as the parameter's, or every update would
            # move its leaf between two layouts
            site, stacked = site_of(sites, path)
            s = zero_partition_spec(leaf.shape, mesh, base_spec=base,
                                    stacked=stacked, leading=site is not None)
        else:
            s = base if base is not None else P()
        return NamedSharding(mesh, s)

    param_shardings = jax.tree_util.tree_map_with_path(
        param_sharding, params_shapes, param_specs,
        is_leaf=lambda x: hasattr(x, "shape"))
    opt_shardings = jax.tree_util.tree_map_with_path(
        opt_sharding, params_shapes, param_specs,
        is_leaf=lambda x: hasattr(x, "shape"))
    return param_shardings, opt_shardings


def build_opt_state_shardings(opt_abstract, params_abstract, mesh: Mesh,
                              stage: int, param_specs=None, sites=None):
    """Shardings for an arbitrary optimizer-state pytree.

    Optimizer states are built of (a) subtrees that mirror the params tree
    (Adam m/v, momentum buffers) — those get the per-param ZeRO⊕TP spec —
    and (b) scalars/None — replicated. Subtree matching is structural, so any
    optimizer whose state contains params-shaped pytrees works.
    """
    params_leaves, params_def = jax.tree_util.tree_flatten(params_abstract)
    _, mirrored = build_zero_shardings(params_abstract, mesh, stage=stage,
                                       param_specs=param_specs, sites=sites)
    rep = replicated(mesh)

    def _mirrors_params(sub) -> bool:
        if sub is None:
            return False
        try:
            leaves, treedef = jax.tree_util.tree_flatten(sub)
        except Exception:
            return False
        return (treedef == params_def
                and all(tuple(l.shape) == tuple(p.shape)
                        for l, p in zip(leaves, params_leaves)))

    def handle(sub):
        if _mirrors_params(sub):
            return mirrored
        # lone leaf without a params mirror: shard by its own shape
        if stage >= 1 and getattr(sub, "ndim", 0) > 0:
            return NamedSharding(mesh, zero_partition_spec(tuple(sub.shape), mesh))
        return rep

    # tree_map recursion handles any registered pytree node (FrozenDict,
    # struct dataclasses, ...); is_leaf stops at params-mirroring subtrees
    return jax.tree_util.tree_map(handle, opt_abstract, is_leaf=_mirrors_params)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


# ----------------------------------------------------------------------
# ZeRO-3 at the use site: gather the WEIGHT, scatter its gradient
#
# A model written against this seam declares its use sites
# (``zero3_use_sites()`` on the object the engine trains: param-path
# prefix -> leading scanned dims) and asks :func:`gather_at_use` for the
# weights of each site where it uses them. While the engine traces a
# stage-3 step it runs the loss under ``shard_map`` with a
# :class:`GatherPlan` of the live ZeRO axes active; everywhere else no plan is
# active and the function is the identity.
GATHERED = "zero3_gathered"  # checkpoint_name of every gathered weight

_PLANS: list = []


def use_sites_of(model) -> Dict[str, int]:
    """The use sites a model declares, ``{}`` for one that declares none."""
    sites = getattr(model, "zero3_use_sites", None)
    return dict(sites()) if callable(sites) else {}


def site_of(sites: Optional[Dict[str, int]], path) -> Tuple[Optional[str], int]:
    """``(site, stacked dims)`` of the site a param path lies under
    (``path``: a "/"-joined string or a tree key path); ``(None, 0)``."""
    if not sites:
        return None, 0
    if not isinstance(path, str):
        path = _path_str(path)
    for site, stacked in sites.items():
        if path == site or path.startswith(site + "/"):
            return site, int(stacked)
    return None, 0


def _path_str(key_path) -> str:
    return "/".join(key_entry_str(k) for k in key_path)


def _names(entry) -> Tuple[str, ...]:
    if entry is None:
        return ()
    return tuple(entry) if isinstance(entry, (tuple, list)) else (entry,)


def _entry(names):
    return None if not names else names[0] if len(names) == 1 else tuple(names)


def manual_spec(spec, axes: Sequence[str], ndim: Optional[int] = None) -> P:
    """The part of ``spec`` that names ``axes``: what a ``shard_map`` manual
    over exactly those axes takes for an array laid out as ``spec``."""
    entries = list(spec) if spec is not None else []
    if ndim is not None:
        entries += [None] * (ndim - len(entries))
    return P(*(_entry([a for a in _names(e) if a in axes]) for e in entries))


class GatherPlan:
    """What the engine makes known while it traces a stage-3 step: the
    mesh, every parameter's shape and PartitionSpec, the model's use
    sites and the live ZeRO axes (the ``shard_map``'s manual axes). Also
    the record of what was gathered (:meth:`describe`), filled as the
    model is traced."""

    def __init__(self, mesh: Mesh, shardings, shapes,
                 sites: Dict[str, int],
                 zero_axes: Sequence[str] = ZERO_AXES,
                 batch_axes: Sequence[str] = BATCH_AXES):
        self.mesh = mesh
        self.axes = tuple(a for a in zero_axes if mesh.shape.get(a, 1) > 1)
        self.batch_axes = tuple(a for a in batch_axes if a in self.axes)
        self.world = int(np.prod([mesh.shape[a] for a in self.axes] or [1]))
        self.sites = dict(sites)
        self.specs = jax.tree_util.tree_map(
            lambda sh: getattr(sh, "spec", sh), shardings,
            is_leaf=lambda x: isinstance(x, (NamedSharding, P)))
        self.shapes = jax.tree_util.tree_map(
            lambda x: tuple(x.shape), shapes,
            is_leaf=lambda x: hasattr(x, "shape"))
        self.served: Dict[str, Dict] = {}
        self.ahead: Dict[str, int] = {}  # site -> layers gathered ahead

    def __enter__(self):
        _PLANS.append(self)
        return self

    def __exit__(self, *exc):
        _PLANS.pop()

    # -- what the shard_map takes -----------------------------------------
    def param_in_specs(self):
        return jax.tree_util.tree_map(
            lambda spec, shape: manual_spec(spec, self.axes, len(shape)),
            self.specs, self.shapes, is_leaf=lambda x: isinstance(x, P))

    def batch_in_spec(self, x) -> P:
        entry = axis_spec_entry(self.mesh, self.batch_axes,
                                x.shape[0] if x.ndim else None)
        return P(entry) if x.ndim else P()

    def replica_index(self):
        """This shard's index over the batch axes (0 where the batch is
        not split): what a per-shard rng folds in."""
        idx = 0
        for a in self.batch_axes:
            idx = idx * self.mesh.shape[a] + jax.lax.axis_index(a)
        return idx

    # -- the gather -------------------------------------------------------
    def _leaf(self, w, spec, shape, path: str, stacked: int, dtype,
              keep_dtype: bool):
        """How leaf ``w`` is gathered (:class:`_How`; no dims: it is whole
        already), recorded for :meth:`describe`."""
        entries = list(spec) + [None] * (len(shape) - len(spec))
        dims = [(i - stacked, tuple(a for a in _names(e) if a in self.axes))
                for i, e in enumerate(entries)]
        dims = [(i, axes) for i, axes in dims if axes]
        if not dims:
            return _How((), (), w.dtype, w.dtype)
        if any(i < 0 for i, _ in dims):
            raise ValueError(
                f"{path}: a scanned dim of a ZeRO-3 use site is sharded "
                f"({spec}); declare the site's scanned dims")
        floating = jax.numpy.issubdtype(w.dtype, jax.numpy.floating)
        wire = jax.numpy.dtype(dtype if dtype is not None and floating
                               else w.dtype)
        out = w.dtype if keep_dtype else wire
        trips = int(np.prod(shape[:stacked] or (1,)))
        full = int(np.prod(shape[stacked:]))
        used = [a for _, axes in dims for a in axes]
        sizes = [int(np.prod([self.mesh.shape[a] for a in axes]))
                 for _, axes in dims]
        group = int(np.prod(sizes))
        form = scatter_form(sizes)
        # a ring's permutes and the bytes a chip feeds them: the dims are
        # scattered last to first, each over what the ones before it left
        left, steps, permutes, ring_bytes = full * 4, 0, 0, 0
        if form == "ring":
            for (dim, _), n in zip(reversed(dims), reversed(sizes)):
                left //= n
                steps += n - 1
                permutes += (n - 1) * len(
                    _ring_parts(n, shape[stacked + dim] // n))
                ring_bytes += (n - 1) * left
        self.served[path] = {
            "in_scan": stacked > 0, "trips": trips,
            "gather_operand_bytes": full // group * wire.itemsize,
            "scatter_operand_bytes": full * 4,
            "scatter_form": form, "ring_steps": steps,
            "ring_permutes": permutes, "ring_operand_bytes": ring_bytes,
            "wire_dtype": wire.name}
        return _How(
            tuple((dim, axes, n if form == "ring" else 0)
                  for (dim, axes), n in zip(dims, sizes)),
            tuple(a for a in self.axes if a not in used), wire, out)

    def gather(self, tree, path: Sequence[str], dtype=None, stacked: int = 0,
               keep_dtype: bool = False, together: bool = False):
        """``tree`` (the parameters at ``path``) whole. ``together``: the
        leaves one collective can carry go in one (:func:`_gather_together`:
        a layer's kernels whose shards have the same rows), where fewer,
        larger gathers are what the chip runs beside a layer's matmuls."""
        specs, shapes = self.specs, self.shapes
        for k in path:
            specs, shapes = specs[k], shapes[k]
        prefix = "/".join(path)
        hows = jax.tree_util.tree_map_with_path(
            lambda kp, w, spec, shape: self._leaf(
                w, spec, shape,
                "/".join(filter(None, (prefix, _path_str(kp)))),
                stacked, dtype, keep_dtype),
            tree, specs, shapes)
        if not together:
            return jax.tree_util.tree_map(_gather_leaf, tree, hows)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        return treedef.unflatten(
            _gather_together(leaves, treedef.flatten_up_to(hows)))

    def gather_rest(self, params):
        """Every sharded leaf that lies under NO use site, gathered here,
        once, in its own dtype: whatever the model does not ask for is
        whole before the model sees it."""
        def leaf(kp, w, spec, shape):
            path = _path_str(kp)
            if site_of(self.sites, path)[0] is not None:
                return w
            return _gather_leaf(
                w, self._leaf(w, spec, shape, path, 0, None, False))

        return jax.tree_util.tree_map_with_path(
            leaf, params, self.specs, self.shapes)

    def reduce_rest(self, grads):
        """The gradient of every leaf the plan did not gather (a
        persistent one, whole on every chip) summed over the manual axes
        in float32; a gathered leaf's was reduce-scattered in its
        backward."""
        def leaf(kp, g):
            if _path_str(kp) in self.served or not self.axes:
                return g
            return jax.lax.psum(g.astype(np.float32), self.axes)

        return jax.tree_util.tree_map_with_path(leaf, grads)

    # -- the counter ------------------------------------------------------
    def describe(self) -> Dict:
        """JSON-safe plan of one training step on one chip: which leaves
        are gathered where, and the operand bytes of the all-gathers and
        reduce-scatters (what each chip feeds them; a scanned leaf is
        gathered again in the rematerialised backward, and once more where
        the site gathers ahead). Of the scattered
        gradients, which leaves the ring took (:func:`scatter_form`), in
        how many permutes (trips x steps x ways) and operand bytes: a
        ring over ``n`` chips sends ``(n - 1) / n`` of what it scatters.
        ``gathers_ahead_step``: the leaf gathers a step that run one layer
        ahead of their use, beside the layer before (:func:`gatherer`: the
        forward's, all but the first layer's)."""
        scan = {p: r for p, r in self.served.items() if r["in_scan"]}
        once = {p: r for p, r in self.served.items() if not r["in_scan"]}
        n_leaves = len(jax.tree_util.tree_leaves(
            self.shapes, is_leaf=lambda x: isinstance(x, tuple)))
        ahead = {p: max((n for site, n in self.ahead.items()
                         if p.startswith(site + "/")), default=0)
                 for p in scan}
        # forward and rematerialised backward; a site that gathers ahead
        # gathers once more (its last scan step gathers its own layer again)
        in_scan = sum((2 * r["trips"] + (ahead[p] > 0))
                      * r["gather_operand_bytes"] for p, r in scan.items())
        gathered = in_scan + sum(r["gather_operand_bytes"]
                                 for r in once.values())
        scattered = sum(r["trips"] * r["scatter_operand_bytes"]
                        for r in self.served.values())
        ring = [r for r in self.served.values() if r["ring_steps"]]
        return {"program": "gather_at_use", "axes": list(self.axes),
                "leaves_gathered_in_scan": len(scan),
                "leaves_gathered_once": len(once),
                "leaves_persistent": n_leaves - len(self.served),
                "gather_operand_bytes_step": int(gathered),
                "gather_operand_bytes_in_scan": int(in_scan),
                "gathers_ahead_step": int(sum(
                    max(n - 1, 0) for n in ahead.values())),
                "scatter_operand_bytes_step": int(scattered),
                "leaves_scattered_by_ring": len(ring),
                "ring_permutes_step": int(sum(
                    r["trips"] * r["ring_permutes"] for r in ring)),
                "ring_operand_bytes_step": int(sum(
                    r["trips"] * r["ring_operand_bytes"] for r in ring)),
                "wire_dtypes": sorted({r["wire_dtype"]
                                       for r in self.served.values()})}


# the scatter groups an unrolled ring serves: n - 1 steps each way
RING_GROUPS = range(2, 9)


def scatter_form(groups: Sequence[int]) -> str:
    """How the float32 gradient of a gathered leaf is summed and scattered,
    from what the plan observes of the mesh: ``groups``, the chips each
    sharded dim is split over. ``"ring"`` (:func:`ring_reduce_scatter`)
    where every group is 2 to 8 chips: the ring's permutes are
    asynchronous on the chip, so the rest of the layer's backward runs
    beside a kernel's, and the optimizer's first updates beside a
    table's. ``"reduce_scatter"``, the backend's own (synchronous on the
    chip), for a dim split over more chips than an unrolled ring should
    have steps."""
    return "ring" if groups and all(
        n in RING_GROUPS for n in groups) else "reduce_scatter"


def _ring_parts(n: int, piece: int):
    """How a ring over ``n`` chips cuts a piece of ``piece`` rows:
    ``(first row, rows, hop)`` of each part. Two halves that travel
    opposite ways, so both directions of every link carry half the bytes,
    where the ring has two ways and the piece two rows."""
    half = piece // 2 if n > 2 else 0
    if not half:
        return [(0, piece, 1)]
    return [(0, half, 1), (half, piece - half, -1)]


def ring_reduce_scatter(ct, axes, dim: int, n: int):
    """``psum_scatter(ct, axes, scatter_dimension=dim, tiled=True)`` over a
    group of ``n`` chips written out as a ring in the axes' index order:
    ``ct`` is cut in ``n`` pieces along ``dim``; in each of ``n - 1``
    steps a chip sends one partial sum to its neighbour, receives one and
    adds its own contribution, in ``ct``'s dtype on the wire and in every
    add; after the last step chip ``i`` holds the sum of piece ``i``."""
    piece = ct.shape[dim] // n
    me = jax.lax.axis_index(axes)
    parts = []
    for first, rows, hop in _ring_parts(n, piece):
        perm = [(i, (i + hop) % n) for i in range(n)]

        def own(j, first=first, rows=rows):
            return jax.lax.dynamic_slice_in_dim(
                ct, (j % n) * piece + first, rows, axis=dim)

        # the partial sum that arrives at step s is piece ``me - s * hop``'s
        part = own(me - hop)
        for s in range(2, n + 1):
            part = jax.lax.ppermute(part, axes, perm) + own(me - s * hop)
        parts.append(part)
    return parts[0] if len(parts) == 1 else jax.numpy.concatenate(
        parts, axis=dim)


class _How:
    """How one leaf is gathered: ``dims``, ``(dim, axes, ring)`` of each
    sharded dim (none: the leaf is whole on every chip); ``other_axes``,
    the manual axes it is whole on (hpZ's ``data``); the ``wire`` dtype
    and the dtype it comes ``out`` in."""

    __slots__ = ("dims", "other_axes", "wire", "out")

    def __init__(self, dims, other_axes, wire, out):
        self.dims, self.other_axes = dims, other_axes
        self.wire, self.out = wire, out


def _whole(w, how):
    """``w`` (this chip's shard) cast to the wire dtype ON THE SHARD and
    all-gathered over the ZeRO axes of each sharded dim."""
    g = w.astype(how.wire)
    for dim, axes, _ in how.dims:
        g = jax.lax.all_gather(g, axes, axis=dim, tiled=True)
    return g.astype(how.out)


def _scatter(ct, how, dtype):
    """The gather's transpose, written out: the cotangent is cast UP to
    float32 and reduce-scattered, by a ring of ``ring`` chips
    (:func:`ring_reduce_scatter`) or, ``ring`` 0, by the backend's
    ``psum_scatter`` (:func:`scatter_form`), so every chip ends holding
    its own shard of the float32 sum (and the sum over ``other_axes``)."""
    ct = ct.astype(np.float32)
    for dim, axes, ring in reversed(how.dims):
        ct = ring_reduce_scatter(ct, axes, dim, ring) if ring else \
            jax.lax.psum_scatter(ct, axes, scatter_dimension=dim, tiled=True)
    if how.other_axes:
        ct = jax.lax.psum(ct, how.other_axes)
    return ct.astype(dtype)


def _gather_leaf(w, how):
    """``w`` -> the whole weight (:func:`_whole`), its gradient float32
    and scattered (:func:`_scatter`); ``w`` itself where it is whole."""
    if not how.dims:
        return w
    in_dtype = w.dtype

    @jax.custom_vjp
    def zero3_gather(w):
        return _whole(w, how)

    zero3_gather.defvjp(lambda w: (zero3_gather(w), None),
                        lambda _, ct: (_scatter(ct, how, in_dtype),))
    # named OUTSIDE the custom_vjp, where a remat policy can see it: no
    # policy of this repo saves a gathered weight, backward gathers again
    return checkpoint_name(zero3_gather(w), GATHERED)


def _gather_together(leaves, hows):
    """:func:`_gather_leaf` of every leaf, with the leaves that ONE
    collective can carry sent in one: two-dim leaves split on their
    leading dim alone, over the same axes, with the same rows a shard and
    the same dtype on the wire (a GPT-2 layer's ``c_attn``, attention
    ``c_proj`` and ``c_fc`` kernels) are concatenated along their other
    dim, all-gathered once and cut apart again. The chip runs about one
    gather at a time beside a layer's matmuls and the rest alone (PERF.md,
    PR 47): fewer and larger is what it hides. The transpose stays each
    leaf's own, so a kernel's ring starts as its gradient arrives."""
    carried = {}
    for i, (w, how) in enumerate(zip(leaves, hows)):
        if (len(how.dims) == 1 and how.dims[0][0] == 0 and w.ndim == 2
                and how.out == how.wire):
            carried.setdefault(
                (how.dims[0][1], w.shape[0], how.wire), []).append(i)
    carried = [idx for idx in carried.values() if len(idx) > 1]
    dtypes = [w.dtype for w in leaves]

    @jax.custom_vjp
    def zero3_gather_together(*ws):
        out = list(ws)
        for idx in carried:
            how = hows[idx[0]]
            g = _whole(jax.numpy.concatenate(
                [ws[i].astype(how.wire) for i in idx], axis=1), how)
            cuts = np.cumsum([0] + [ws[i].shape[1] for i in idx])
            for i, lo, hi in zip(idx, cuts[:-1], cuts[1:]):
                out[i] = g[:, lo:hi]
        alone = set(range(len(ws))) - {i for idx in carried for i in idx}
        for i in alone:
            if hows[i].dims:
                out[i] = _whole(ws[i], hows[i])
        return tuple(out)

    zero3_gather_together.defvjp(
        lambda *ws: (zero3_gather_together(*ws), None),
        lambda _, cts: tuple(
            _scatter(ct, how, dtype) if how.dims else ct
            for ct, how, dtype in zip(cts, hows, dtypes)))
    return list(zero3_gather_together(*leaves))


def gather_at_use(tree, path: Sequence[str], dtype=None, stacked: int = 0,
                  keep_dtype: bool = False):
    """THE SEAM. ``tree``: the parameters at ``path`` of the model's
    parameter tree (a leaf or a subtree), as the model is about to use
    them; ``stacked``: how many leading scanned dims a ``lax.scan`` has
    already sliced off them. Returns them whole: each leaf that ZeRO-3
    shards is cast to ``dtype`` (floating leaves; ``None`` keeps the
    leaf's own) on the shard, all-gathered over its ZeRO axes, and its
    gradient is reduce-scattered in float32 (:func:`_gather_leaf`).
    ``keep_dtype`` casts the gathered values back up to the leaf's dtype:
    for a table whose cotangents are accumulated in it (an embedding
    looked up and tied to the head), so that only the wire is narrow.

    The identity unless a :class:`GatherPlan` is active, which the engine
    enters only around a stage-3 step on a mesh whose ZeRO axes multiply
    to more than one, and even then for every leaf whose spec names no
    such axis."""
    plan = _PLANS[-1] if _PLANS else None
    if plan is None:
        return tree
    return plan.gather(tree, tuple(path), dtype, stacked, keep_dtype)


def gatherer(path: Sequence[str], dtype=None, stacked: int = 0,
             ahead: int = 0):
    """:func:`gather_at_use` at ``path`` as a function of the tree, bound
    to the plan that is active NOW: for a use site that gathers outside
    the trace the plan was entered for (a ``custom_vjp``'s backward rule
    is traced after the loss has returned). ``ahead``: the function is
    the site's PREFETCH, which gathers each of that many layers' weights
    one layer before its use; the plan counts it (``gathers_ahead_step``)
    and sends the leaves it can in one collective
    (:func:`_gather_together`), because what runs ahead has to hide
    beside the layer before."""
    plan, path = _PLANS[-1], tuple(path)
    if ahead:
        plan.ahead["/".join(path)] = int(ahead)
    return lambda tree: plan.gather(tree, path, dtype, stacked,
                                    together=bool(ahead))


def gathering() -> bool:
    """Whether a plan is active: a block stack is the use site of its
    own weights only then, so every other trace is the plain module's."""
    return bool(_PLANS)


# ----------------------------------------------------------------------
# SpecLayout: the one authority over the data x fsdp x tp mesh layout
class SpecLayout:
    """Canonical named-axis partition layout (GSPMD, arXiv:2105.04663).

    ONE object answers every "which axis shards this tensor?" question
    for a mesh, consumed identically by training and inference:

    - **parameter families** (embeddings, attention QKV, attention
      output proj, MLP in, MLP out, norms) get tp-axis base
      PartitionSpecs from a ``module_inject`` policy;
    - **ZeRO** (stages 1-3) layers ``data x fsdp x expert`` sharding on
      the dims TP left alone (:func:`zero_partition_spec`);
    - **batch arrays** shard over ``batch_axes`` ONLY — by contract
      ``fsdp`` and ``tp`` never appear in a batch spec (they shard
      weights/heads, so putting them on the batch would silently change
      the global batch size).

    ``policy`` may be a TPPolicy, a policy name, or None (name "auto").
    """

    def __init__(self, mesh: Mesh, policy="auto",
                 tp_axis: str = AXIS_TP,
                 zero_axes: Sequence[str] = ZERO_AXES,
                 batch_axes: Sequence[str] = BATCH_AXES,
                 persistence_threshold: int = 0,
                 hierarchical_gather: bool = False):
        forbidden = {tp_axis, AXIS_FSDP} & set(batch_axes)
        if forbidden:
            raise ValueError(
                f"batch_axes {tuple(batch_axes)} must not contain the "
                f"tp/fsdp axes {sorted(forbidden)}: they shard weights, "
                "never the batch dimension")
        from deepspeed_tpu.parallel.topology import resolve_axis_name

        self.mesh = mesh
        # a user-built mesh may still carry the legacy "model" axis name
        # — specs must name the axis the mesh actually has, or TP would
        # silently replicate
        self.tp_axis = resolve_axis_name(mesh, tp_axis)
        self.zero_axes = tuple(zero_axes)
        self.batch_axes = tuple(batch_axes)
        self.persistence_threshold = int(persistence_threshold)
        self.hierarchical_gather = bool(hierarchical_gather)
        self._policy = policy

    @property
    def hierarchical_active(self) -> bool:
        """hpZ in effect: requested AND the mesh has a secondary (non-data)
        ZeRO axis of size > 1 to hold the replica-local shard. On a flat
        data-only mesh the flag is a no-op — the caller (engine) warns."""
        if not self.hierarchical_gather:
            return False
        return any(self.mesh.shape.get(a, 1) > 1
                   for a in hierarchical_param_axes(self.zero_axes))

    # -- policy / families ------------------------------------------------
    @property
    def policy(self):
        from deepspeed_tpu.module_inject.policies import get_tp_policy

        if isinstance(self._policy, str) or self._policy is None:
            self._policy = get_tp_policy(self._policy or "auto")
        return self._policy

    @property
    def tp_size(self) -> int:
        return int(self.mesh.shape.get(self.tp_axis, 1))

    def family_of(self, path: str, shape: Tuple[int, ...] = ()) -> str:
        """Parameter family of one param path (module docstring list)."""
        from deepspeed_tpu.module_inject.policies import family_for

        return family_for(path, shape, self.policy)

    def base_spec(self, path: str, shape: Tuple[int, ...]) -> Optional[P]:
        """TP base PartitionSpec for one param (None = replicated)."""
        return self.policy.spec_for(path, tuple(shape), self.tp_size,
                                    self.tp_axis)

    def base_specs(self, params_abstract):
        """Pytree of tp-axis base specs for a whole param tree."""
        from deepspeed_tpu.module_inject.policies import specs_from_policy

        return specs_from_policy(self.policy, params_abstract, self.mesh,
                                 axis=self.tp_axis)

    # -- ZeRO layering ----------------------------------------------------
    def param_spec(self, shape, base_spec=None, stage: int = 3) -> P:
        """Final spec of a parameter under ``stage`` (TP ⊕ ZeRO-3).
        With :attr:`hierarchical_active`, the ZeRO layer spans only the
        non-data axes (hpZ — the per-use gather stays in-replica)."""
        if stage >= 3:
            axes = hierarchical_param_axes(self.zero_axes) \
                if self.hierarchical_active else self.zero_axes
            return zero_partition_spec(
                tuple(shape), self.mesh, data_axes=axes,
                base_spec=base_spec,
                persistence_threshold=self.persistence_threshold)
        return base_spec if base_spec is not None else P()

    def opt_spec(self, shape, base_spec=None, stage: int = 1) -> P:
        """Final spec of an optimizer-state leaf under ``stage``."""
        if stage >= 1:
            return zero_partition_spec(tuple(shape), self.mesh,
                                       data_axes=self.zero_axes,
                                       base_spec=base_spec)
        return base_spec if base_spec is not None else P()

    def shardings(self, params_abstract, stage: int):
        """(param_shardings, opt_shardings) — build_zero_shardings fed
        by this layout's policy/axes/threshold."""
        return build_zero_shardings(
            params_abstract, self.mesh, stage=stage,
            param_specs=self.base_specs(params_abstract),
            persistence_threshold=self.persistence_threshold,
            hierarchical=self.hierarchical_active)

    # -- batch ------------------------------------------------------------
    def batch_spec(self, ndim: int = 2,
                   shape: Optional[Tuple[int, ...]] = None) -> P:
        """Batch arrays: leading dim over ``batch_axes``; with sequence
        parallelism active, dim 1 (tokens) additionally shards over
        ``seq``. Dims not divisible by their axis product stay unsharded
        (requires ``shape``). Never names fsdp/tp (class contract)."""
        from deepspeed_tpu.parallel.topology import axis_spec_entry

        entries = [None] * ndim
        entries[0] = axis_spec_entry(self.mesh, self.batch_axes,
                                     shape[0] if shape is not None else None)
        if ndim >= 2:
            entries[1] = axis_spec_entry(
                self.mesh, (AXIS_SEQ,),
                shape[1] if shape is not None else None)
        return P(*entries)

    def batch_sharding(self, ndim: int = 2,
                       shape: Optional[Tuple[int, ...]] = None
                       ) -> NamedSharding:
        return NamedSharding(self.mesh, self.batch_spec(ndim, shape))

    # -- identity ---------------------------------------------------------
    def describe(self) -> Dict:
        """JSON-safe identity of this layout: the axis roles plus one
        canonical spec per parameter family at the live tp size — what
        the docs render and the fingerprint/manifest can embed."""
        tp = self.tp_size
        families = {
            "embedding": spec_entries(P(self.tp_axis, None) if tp > 1
                                      else None),
            "attn_qkv": spec_entries(P(None, self.tp_axis) if tp > 1
                                     else None),
            "attn_proj": spec_entries(P(self.tp_axis, None) if tp > 1
                                      else None),
            "mlp_in": spec_entries(P(None, self.tp_axis) if tp > 1
                                   else None),
            "mlp_out": spec_entries(P(self.tp_axis, None) if tp > 1
                                    else None),
            "norm": spec_entries(None),
        }
        return {
            "policy": getattr(self.policy, "name", "auto"),
            "tp_axis": self.tp_axis,
            "tp_size": tp,
            "zero_axes": list(self.zero_axes),
            "batch_axes": list(self.batch_axes),
            "hierarchical_gather": self.hierarchical_active,
            "families": families,
        }


def default_layout(mesh: Mesh, policy="auto",
                   persistence_threshold: int = 0) -> SpecLayout:
    """The repo-wide default SpecLayout for a mesh (canonical axis
    roles; the knobs engines thread through come from their configs)."""
    return SpecLayout(mesh, policy=policy,
                      persistence_threshold=persistence_threshold)


# ----------------------------------------------------------------------
# PartitionSpec <-> JSON (the topology-manifest wire format: a checkpoint
# must record how every logical tensor was partitioned at save time so a
# restore onto a DIFFERENT mesh can validate and reshard deliberately)
def spec_entries(spec) -> list:
    """JSON-safe form of a PartitionSpec: one entry per dim — ``None``,
    an axis name, or a list of axis names."""
    if spec is None:
        return []
    out = []
    for e in spec:
        if e is None:
            out.append(None)
        elif isinstance(e, (tuple, list)):
            out.append([str(a) for a in e])
        else:
            out.append(str(e))
    return out


def sharding_spec_entries(sharding) -> list:
    """JSON-safe partition spec of a (Named)Sharding; fully-replicated /
    unknown sharding kinds serialize as ``[]``."""
    spec = getattr(sharding, "spec", None)
    return spec_entries(spec)


def batch_sharding(mesh: Mesh, data_axes: Optional[Sequence[str]] = None,
                   ndim: int = 2, shape: Optional[Tuple[int, ...]] = None) -> NamedSharding:
    """Batch arrays, per the default :class:`SpecLayout`: leading dim
    over the layout's ``batch_axes`` (data x expert — NEVER fsdp/tp,
    which shard weights); with sequence parallelism active, dim 1
    (tokens) additionally shards over ``seq``. Dims not divisible by
    their axis product stay unsharded (requires ``shape``). An explicit
    ``data_axes`` builds a one-off layout with those batch axes."""
    layout = SpecLayout(mesh) if data_axes is None \
        else SpecLayout(mesh, batch_axes=tuple(data_axes))
    return layout.batch_sharding(ndim=ndim, shape=shape)
