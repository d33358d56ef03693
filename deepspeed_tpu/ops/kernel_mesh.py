"""Where a Pallas (Mosaic) kernel call sits on a mesh: the one place that
knows.

A ``pallas_call`` on the TPU is a custom call GSPMD cannot partition
("Mosaic kernels cannot be automatically partitioned. Please wrap the
call in a shard_map", the chip's compiler says, and it says so whenever
ANY axis of the mesh, of size one included, is not manual). So:

- one device, no mesh: the plain kernel;
- a mesh of more than one device, called from ordinary (GSPMD) code: a
  ``shard_map`` over the whole mesh, batch over the data axes, heads over
  tp, tokens over seq where each divides;
- called from inside a ``shard_map`` that already made EVERY axis manual
  (the Ulysses / ring bodies): the plain kernel again, on the local shard;
- called from inside a ``shard_map`` that left axes Auto (the pipeline
  engine's, manual over ``pipe`` only): a nested ``shard_map`` over the
  axes still Auto, on the context's own mesh.

The flash wrappers (``ops/flash_attention.py``) and the decode ``*_tp``
wrappers (``ops/decode_attention.py``) all ask :func:`kernel_mesh_plan`
and nothing else resolves mesh, topology or axes for a kernel call.
"""

from typing import Any, FrozenSet, NamedTuple, Optional

import jax


class KernelMeshPlan(NamedTuple):
    mesh: Any                          # what shard_map takes
    axis_names: Optional[FrozenSet]    # None: the whole mesh; nested: the
    #                                    axes the enclosing shard_map left Auto
    batch: Any                         # spec entry of the batch dim
    heads: Optional[str]               # ... of the head dim (the tp axis)
    seq: Optional[str]                 # ... of the token dim (the seq axis)

    def size(self, entry) -> int:
        """How many ways a spec entry of this plan splits its dim."""
        if entry is None:
            return 1
        names = entry if isinstance(entry, tuple) else (entry,)
        n = 1
        for a in names:
            n *= int(self.mesh.shape[a])
        return n

    def shard_map(self, f, in_specs, out_specs, name: Optional[str] = None):
        """``f`` per shard. A kernel that carries no name of its own
        (``pl.pallas_call(name=...)``) would print in the device trace
        as ``shard_map.N``: ``name`` is the scope its body runs in
        instead."""
        from deepspeed_tpu.utils.compat import shard_map

        if name is not None:
            body = f

            def f(*args):
                with jax.named_scope(name):
                    return body(*args)

        return shard_map(f, mesh=self.mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False,
                         axis_names=self.axis_names)


def kernel_mesh_plan(batch: int, heads: int, seqlen: Optional[int] = None,
                     mesh=None, axis: Optional[str] = None,
                     seq_axis: Optional[str] = None
                     ) -> Optional[KernelMeshPlan]:
    """The ``shard_map`` a kernel call over ``[batch, ..., heads, ...]``
    must sit in, or ``None`` when the plain kernel serves (see the module
    docstring). ``mesh`` defaults to the global topology's; inside a
    ``shard_map`` the trace context's mesh wins. The head entry is the
    resolved tp axis (the legacy "model" alias included) when it is live
    and divides the heads. The seq entry is asked for by passing
    ``seqlen`` and given only when both the post-tp head group and the
    tokens divide (Ulysses trades heads for tokens). The batch entry keeps
    the data axes sharding the batch INSIDE the shard_map: omitting it
    would all-gather the batch whenever tp/sp compose with data>1."""
    from deepspeed_tpu.parallel.topology import (AXIS_SEQ, AXIS_TP,
                                                 axis_spec_entry,
                                                 get_topology,
                                                 resolve_axis_name)
    from deepspeed_tpu.runtime.zero.partition import BATCH_AXES

    ctx = jax.sharding.get_abstract_mesh()
    manual = frozenset() if ctx.empty else frozenset(ctx.manual_axes)
    if manual:
        mesh = ctx
        auto = frozenset(ctx.axis_names) - manual
        if not auto:
            return None
    else:
        if mesh is None:
            topo = get_topology(create_if_missing=False)
            mesh = topo.mesh if topo is not None else None
        if mesh is None or mesh.size == 1:
            return None
        auto = None

    def free(*axes):
        return tuple(a for a in axes if a not in manual)

    tp_axis = resolve_axis_name(mesh, axis or AXIS_TP)
    head_entry = axis_spec_entry(mesh, free(tp_axis), heads)
    seq_entry = None
    if seqlen is not None:
        sp_axis = resolve_axis_name(mesh, seq_axis or AXIS_SEQ)
        tp = int(mesh.shape[head_entry]) if head_entry else 1
        sp = int(mesh.shape.get(sp_axis, 1)) if free(sp_axis) else 1
        if sp > 1 and (heads // tp) % sp == 0 and seqlen % sp == 0:
            seq_entry = sp_axis
    return KernelMeshPlan(mesh, auto,
                          axis_spec_entry(mesh, free(*BATCH_AXES), batch),
                          head_entry, seq_entry)
