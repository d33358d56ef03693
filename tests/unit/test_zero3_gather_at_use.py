"""ZeRO-3 gathers the weights, not the activations: the program, pinned.

Under the engine's stage-3 step a model written against the seam
(``runtime/zero/partition.gather_at_use``; the scanned GPT-2) runs under
``shard_map`` over the live ZeRO axes: inside the layer loop one layer's
weights are all-gathered in the compute dtype and their float32 gradients
summed and scattered by a ring of float32 ``collective-permute``s, as are
those of the tables gathered once; in the forward pass a layer's weights
are gathered one layer ahead of their use, carried into the scan step that
uses them and saved by none; activations never leave the batch
layout, so there is no ``all-to-all`` and every permute's operand is a
piece of a WEIGHT's gradient. What the compiled step gathers and permutes
is what the plan the engine logs says.
Everywhere else (lower stages, one device, serving, a model that declares
no use site) the seam is the identity and the program is the parent's.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models.gpt2 import (GPT2Config, GPT2ForTraining,
                                       GPT2LMHeadModel)
from deepspeed_tpu.parallel.topology import MeshTopology, reset_topology
from deepspeed_tpu.runtime.zero import partition as zero
from deepspeed_tpu.utils.hlo_inspect import collectives_per_step

LAYERS, WIDTH, HEADS, VOCAB, SEQ = 3, 64, 4, 256, 32
# a layer's kernels (4,096 to 16,384 elements) and the tables are sharded;
# its biases and norms (64 to 256) are persistent
THRESHOLD = 1000


@pytest.fixture(autouse=True)
def _fresh_topology():
    reset_topology()
    yield
    reset_topology()


def _engine(stage, axes, dtype=jnp.float32, hierarchical=False, scan=True,
            policy="dots", micro=2, layers=LAYERS, **model_kw):
    reset_topology()
    n = int(np.prod(list(axes.values())))
    model = GPT2ForTraining(GPT2Config(
        vocab_size=VOCAB, n_positions=SEQ, n_embd=WIDTH, n_layer=layers,
        n_head=HEADS, dtype=dtype, scan_layers=scan,
        remat=policy is not None, remat_policy=policy or "full",
        **model_kw))
    config = {
        "train_micro_batch_size_per_gpu": micro,
        "gradient_accumulation_steps": 1,
        "optimizer": {"type": "AdamW",
                      "params": {"lr": 1e-3, "weight_decay": 0.1}},
        "gradient_clipping": 1.0,
        "bf16": {"enabled": dtype == jnp.bfloat16},
        "fused_step": True,
        "zero_optimization": {"stage": stage,
                              "param_persistence_threshold": THRESHOLD,
                              "hierarchical_gather": hierarchical},
        "steps_per_print": 10 ** 9, "seed": 7}
    engine, *_ = deepspeed_tpu.initialize(
        model=model, config=config,
        mesh=MeshTopology(axis_sizes=axes, devices=jax.devices()[:n]))
    return engine


def _batch(engine, axes, micro=2, step=0):
    rows = micro * axes.get("data", 1)
    ids = np.random.default_rng(step).integers(
        0, VOCAB, (rows, SEQ), dtype=np.int32)
    return {"input_ids": ids}


def _train(engine, axes, steps=2):
    losses = []
    for i in range(steps):
        loss = engine(_batch(engine, axes, step=i))
        engine.backward(loss)
        engine.step()
        losses.append(float(loss))
    return losses


def _step_text(engine, axes):
    """The compiled fused step (the engine's own jitted program)."""
    engine(_batch(engine, axes))  # builds the state and the program
    return engine._jit_fused.lower(
        engine.state, engine._shard_batch(_batch(engine, axes)),
        jnp.float32(0)).compile().as_text()


def _lowered_text(engine, axes):
    """The step as JAX hands it to the compiler (StableHLO)."""
    return engine._jit_fused.lower(
        engine.state, engine._shard_batch(_batch(engine, axes)),
        jnp.float32(0)).as_text()


MESHES = [
    ({"data": 4}, False), ({"fsdp": 4}, False),
    ({"data": 2, "fsdp": 2}, False), ({"data": 2, "fsdp": 2}, True),
    ({"data": 8}, False),
    ({"data": 4, "fsdp": 2}, True),
]


MESH_IDS = ["+".join(f"{a}{n}" for a, n in axes.items())
            + ("-hpz" if h else "") for axes, h in MESHES]
# a layer's four kernels and the two tables, each split on its leading dim
KERNELS = [(WIDTH, 3 * WIDTH), (WIDTH, WIDTH), (WIDTH, 4 * WIDTH),
           (4 * WIDTH, WIDTH)]
TABLES = [(VOCAB, WIDTH), (SEQ, WIDTH)]
KERNEL_ELEMENTS = sum(r * c for r, c in KERNELS)
TABLE_ELEMENTS = sum(r * c for r, c in TABLES)


def _scatter_axes(axes, hierarchical):
    """The axes a parameter is split over: hpZ keeps ``data`` out."""
    return tuple(a for a in ("data", "fsdp") if axes.get(a, 1) > 1
                 and not (hierarchical and a == "data"))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("axes,hierarchical", MESHES, ids=MESH_IDS)
def test_the_compiled_step_gathers_weights_and_scatters_gradients(
        axes, hierarchical, dtype):
    engine = _engine(3, axes, dtype, hierarchical)
    text = _step_text(engine, axes)
    plan = engine._zero3_program
    assert plan["program"] == "gather_at_use"
    zero_axes = [a for a in ("data", "fsdp") if axes.get(a, 1) > 1]
    assert plan["axes"] == zero_axes
    # a layer's four kernels in the scan, wte and wpe once, the rest whole
    assert plan["leaves_gathered_in_scan"] == 4
    assert plan["leaves_gathered_once"] == 2
    assert plan["wire_dtypes"] == [jnp.dtype(dtype).name]
    # every mesh here scatters over 2 to 8 chips: the kernels and the
    # tables take the ring, in n - 1 steps, two ways where the ring has two
    group = int(np.prod([axes[a] for a in _scatter_axes(axes, hierarchical)]))
    ways = 2 if group > 2 else 1
    assert plan["leaves_scattered_by_ring"] == 6
    assert plan["ring_permutes_step"] == \
        (LAYERS * 4 + 2) * (group - 1) * ways
    assert plan["scatter_operand_bytes_step"] == \
        (LAYERS * KERNEL_ELEMENTS + TABLE_ELEMENTS) * 4
    assert plan["ring_operand_bytes_step"] == \
        plan["scatter_operand_bytes_step"] * (group - 1) // group

    every = collectives_per_step(text)
    colls = [c for c in every if c["operand_bytes"] >= 1024]
    in_loop = [c for c in colls if c["trips"] == LAYERS]
    assert {"all-gather", "collective-permute"} <= {c["op"] for c in in_loop}
    # the backend's reduce-scatter is nowhere, and nothing moves an
    # activation between layouts: no all-to-all, and every permute is the
    # ring's, inside the gather's replica groups (hpZ gathers inside a
    # data replica): a kernel's in the layer loop, a table's outside it.
    # (hpZ also moves the gradient SHARDS from the parameters' layout to
    # the optimizer's, ACROSS those groups, once, outside the loop.)
    assert not [c for c in every
                if c["op"] in ("all-to-all", "reduce-scatter")]
    gathers = [c for c in colls if c["op"] == "all-gather"]
    in_loop_gathers = [c for c in gathers if c["trips"] == LAYERS]
    assert {c["group_size"] for c in in_loop_gathers} == {group}
    together = {frozenset(g) for c in in_loop_gathers for g in c["groups"]}
    permutes = [c for c in every if c["op"] == "collective-permute"
                and all(any({src, dst} <= g for g in together)
                        for src, dst in c["pairs"])]
    moved = [c for c in every if c["op"] == "collective-permute"
             and c not in permutes]
    assert not [c for c in moved if c["trips"] > 1 or not hierarchical]
    tables = [c for c in permutes if c["trips"] == 1]
    assert len(permutes) - len(tables) == 4 * (group - 1) * ways
    assert all(c["trips"] == LAYERS for c in permutes if c not in tables)

    # elements a step and chip: the plan the engine logs. (The CPU backend
    # widens a bf16 collective to float32, so the compiled text is counted
    # in elements and the wire's dtype is read from the lowered program;
    # tests/unit/test_chip_compile.py reads the chip's own.)
    wire = jnp.dtype(dtype).itemsize
    widths = {"bf16": 2, "f32": 4}

    def elements(cs):
        return sum(c["trips"] * b // widths[d]
                   for c in cs for d, b in c["operands"])

    # (forward and backward loop; the plan also counts the first layer's
    # gather before the forward loop, whose last step gathers its own
    # layer again)
    assert elements(in_loop_gathers) == 2 * LAYERS * KERNEL_ELEMENTS // group
    assert plan["gather_operand_bytes_in_scan"] // wire \
        == (2 * LAYERS + 1) * KERNEL_ELEMENTS // group
    assert sum(c["trips"] for c in permutes) == plan["ring_permutes_step"]
    assert elements(permutes) == plan["ring_operand_bytes_step"] // 4
    assert elements(tables) == TABLE_ELEMENTS * (group - 1) // group
    assert all({d for d, _ in c["operands"]} == {"f32"} for c in permutes)
    # every permute's operand is a float32 piece of a weight's gradient
    pieces = {(rows // group // ways, cols) for rows, cols in KERNELS + TABLES}
    assert {dims for c in permutes for dims in c["operand_dims"]} == pieces
    lowered = _lowered_text(engine, axes)
    gathered = re.findall(r"all_gather.*?->\s*tensor<[0-9x]*x(\w+)>", lowered)
    assert gathered and set(gathered) == {
        "bf16" if dtype == jnp.bfloat16 else "f32"}
    # the sum across chips is float32 whatever the compute dtype
    summed = re.findall(r"collective_permute.*?->\s*tensor<[0-9x]*x(\w+)>",
                        lowered, re.S)
    assert summed and set(summed) == {"f32"}
    assert "reduce_scatter" not in lowered
    # hpZ: the ring's sum is then summed over ``data``
    if hierarchical:
        psums = [c for c in colls if c["op"] == "all-reduce"
                 and c["group_size"] == axes["data"]]
        assert elements(psums) == \
            (LAYERS * KERNEL_ELEMENTS + TABLE_ELEMENTS) // group


@pytest.mark.parametrize("axes,hierarchical", MESHES, ids=MESH_IDS)
def test_the_ring_is_the_reduce_scatter(axes, hierarchical):
    """On random float32 cotangents the ring leaves what
    ``psum_scatter(tiled=True)`` leaves, to float32 rounding (the sum's
    order differs): piece ``i`` of the sum on index ``i`` of the axes, for
    a scatter along either dim, two ways (pieces of 6 and 5) and one (a
    piece of a single row)."""
    from jax.sharding import Mesh, PartitionSpec as P

    from deepspeed_tpu.utils.compat import shard_map

    names = _scatter_axes(axes, hierarchical)
    n = int(np.prod([axes[a] for a in names]))
    mesh = Mesh(np.array(jax.devices()[:n]).reshape(
        [axes[a] for a in names]), names)
    for dim, shape in [(0, (n * 6, 10)), (1, (3, n * 5)), (0, (n, 7))]:
        ct = jax.random.normal(jax.random.PRNGKey(dim), (n,) + shape,
                               jnp.float32)

        def both(ct, dim=dim):
            return (zero.ring_reduce_scatter(ct[0], names, dim, n)[None],
                    jax.lax.psum_scatter(ct[0], names, scatter_dimension=dim,
                                         tiled=True)[None])

        ring, backend = jax.jit(shard_map(
            both, mesh=mesh, in_specs=P(names),
            out_specs=(P(names), P(names)), check_vma=False))(ct)
        whole = np.asarray(ct, np.float64).sum(0)
        want = np.stack(np.split(whole, n, axis=dim))
        scale = np.abs(want).max()
        assert ring.dtype == jnp.float32
        np.testing.assert_allclose(ring, want, rtol=0, atol=1e-6 * scale)
        np.testing.assert_allclose(ring, backend, rtol=0, atol=1e-6 * scale)


@pytest.mark.parametrize("groups,form", [
    ([2], "ring"), ([4], "ring"), ([8], "ring"), ([2, 4], "ring"),
    ([1], "reduce_scatter"), ([9], "reduce_scatter"),
    ([256], "reduce_scatter"), ([4, 16], "reduce_scatter"),
    ([], "reduce_scatter"),
])
def test_which_leaves_take_the_ring(groups, form):
    """A leaf whose every scatter group is 2 to 8 chips; a group of one
    and a group over 8 keep the backend's ``psum_scatter``."""
    assert zero.scatter_form(groups) == form


def test_a_ring_goes_both_ways_where_it_has_two():
    """A piece is cut in two halves that travel opposite ways; two chips
    have one way round, and a piece of one row does not split."""
    assert [hop for n in (3, 8) for _, _, hop in zero._ring_parts(n, 6)] \
        == [1, -1, 1, -1]
    assert zero._ring_parts(2, 6) == [(0, 6, 1)]
    assert zero._ring_parts(4, 1) == [(0, 1, 1)]
    assert zero._ring_parts(4, 5) == [(0, 2, 1), (2, 3, -1)]


@pytest.mark.parametrize("policy", ["dots", "full", None])
def test_the_scan_saves_no_gathered_weight(policy):
    """Backward gathers again: nowhere in the program is there an array of
    a whole layer's weight times ``n_layer`` (the residual a gather outside
    the rematerialised region would leave), in any dtype; with remat off
    (``None``) the block's own policy keeps every residual but those.
    ``[n_layer, width, width]`` is left out: it is also the shape of the stacked
    SHARDS of the MLP's output projection."""
    axes = {"data": 4}
    engine = _engine(3, axes, jnp.bfloat16, policy=policy)
    text = _step_text(engine, axes)
    assert engine._zero3_program["program"] == "gather_at_use"
    for rows, cols in [(WIDTH, 3 * WIDTH), (WIDTH, 4 * WIDTH),
                       (4 * WIDTH, WIDTH)]:
        assert not re.search(rf"\[{LAYERS},{rows},{cols}\]", text), (rows,
                                                                     cols)
    # the shards are there, stacked
    assert re.search(rf"f32\[{LAYERS},{WIDTH // 4},{3 * WIDTH}\]", text)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-6),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["f32", "bf16"])
def test_stage_3_agrees_with_stage_0(dtype, tol):
    """Two steps on data=4 from the same seed: losses, the evaluation
    loss and the updated parameters agree with the stage-0 program's."""
    axes = {"data": 4}
    e0 = _engine(0, axes, dtype)
    l0 = _train(e0, axes)
    ev0 = float(e0.eval_batch(_batch(e0, axes, step=9)))
    p0 = jax.tree_util.tree_map(np.asarray, e0.state.params)
    n0 = float(e0.get_global_grad_norm() or 0.0)
    e3 = _engine(3, axes, dtype)
    l3 = _train(e3, axes)
    ev3 = float(e3.eval_batch(_batch(e3, axes, step=9)))
    n3 = float(e3.get_global_grad_norm() or 0.0)
    np.testing.assert_allclose(l3, l0, rtol=tol, atol=tol)
    np.testing.assert_allclose(ev3, ev0, rtol=tol, atol=tol)
    np.testing.assert_allclose(n3, n0, rtol=10 * tol, atol=tol)
    for a, b in zip(jax.tree_util.tree_leaves(p0),
                    jax.tree_util.tree_leaves(e3.state.params)):
        # AdamW's first steps move a weight by lr (1e-3) whichever way
        # the gradient's sign falls: bf16 rounding may flip a near-zero
        # one, in both steps
        np.testing.assert_allclose(np.asarray(b), a, rtol=0,
                                   atol=2e-6 if dtype == jnp.float32
                                   else 4.5e-3)


# ---------------------------------------------------------------------------
# one layer ahead: WHEN a scanned layer's weights are gathered
def _scans(jaxpr):
    """``(length, reverse, carry avals, ys avals)`` of every ``scan`` in
    a jaxpr, however deeply nested."""
    found = []

    def walk(j):
        for eqn in getattr(j, "jaxpr", j).eqns:
            if eqn.primitive.name == "scan":
                p = eqn.params
                outs = [v.aval for v in eqn.outvars]
                found.append((p["length"], p["reverse"],
                              outs[:p["num_carry"]], outs[p["num_carry"]:]))
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                    if hasattr(sub, "eqns") or hasattr(sub, "jaxpr"):
                        walk(sub)

    walk(jaxpr)
    return found


def _step_jaxpr(engine, axes):
    engine(_batch(engine, axes))
    return engine._jit_fused.trace(
        engine.state, engine._shard_batch(_batch(engine, axes)),
        jnp.float32(0)).jaxpr


def _shapes(avals, dtype=None):
    return [tuple(a.shape) for a in avals
            if dtype is None or a.dtype == dtype]


@pytest.mark.parametrize("policy", ["dots", "full", None])
@pytest.mark.parametrize("layers", [1, 2, 5])
def test_the_forward_scan_carries_the_next_layers_weights(layers, policy):
    """The forward scan's carry holds one layer's four kernels WHOLE, in
    the compute dtype (gathered by the step before, the first before the
    loop), beside the activations; what the scan saves a layer (its
    ``ys``) holds none of them, and the backward scan carries only the
    activations' cotangent: it gathers inside its own step."""
    axes = {"data": 4}
    engine = _engine(3, axes, jnp.bfloat16, policy=policy, layers=layers)
    forward, backward = _scans(_step_jaxpr(engine, axes))
    assert (forward[:2], backward[:2]) == ((layers, False), (layers, True))
    for kernel in KERNELS:
        assert kernel in _shapes(forward[2], jnp.bfloat16)
        assert kernel not in _shapes(forward[3])
        assert (layers,) + kernel not in _shapes(forward[3])
    assert _shapes(backward[2]) == [(2, SEQ, WIDTH)]
    plan = engine._zero3_program
    assert plan["gathers_ahead_step"] == 4 * (layers - 1)
    # eval_batch's forward takes the same road
    engine.eval_batch(_batch(engine, axes))
    (evaluation,) = _scans(engine._jit_eval.trace(
        engine.state.params,
        engine._shard_batch(_batch(engine, axes))).jaxpr)
    assert all(k in _shapes(evaluation[2], jnp.bfloat16) for k in KERNELS)


def test_the_compiled_forward_gathers_a_layer_in_two_collectives():
    """The prefetch sends a layer's three kernels whose shards have the
    same rows (``c_attn``, attention ``c_proj``, ``c_fc``: 16 of 64) in
    ONE all-gather, concatenated along their columns, and the MLP's
    ``c_proj`` (64 rows of 256) in its own: ``LAYERS`` times in the
    forward loop (the next layer's; the last step's is its own again)
    and once before it (the first layer's). The backward loop gathers
    inside its own step, leaf by leaf as it did. The plan counts all of
    them, leaf by leaf."""
    axes = {"data": 4}
    engine = _engine(3, axes, jnp.bfloat16)
    text = _step_text(engine, axes)
    plan = engine._zero3_program
    gathers = [c for c in collectives_per_step(text)
               if c["op"] == "all-gather" and c["operand_bytes"] >= 1024]
    widths = {"bf16": 2, "f32": 4}

    def elements(cs):
        return sorted(b // widths[d] for c in cs for d, b in c["operands"])

    apart = [rows // 4 * cols for rows, cols in KERNELS]
    together = [sum(apart[:3]), apart[3]]
    tables = [rows // 4 * cols for rows, cols in TABLES]
    assert elements(c for c in gathers if c["trips"] == 1) \
        == sorted(together + tables)
    assert elements(c for c in gathers if c["trips"] == LAYERS) \
        == sorted(together + apart)
    assert (WIDTH // 4, 3 * WIDTH + WIDTH + 4 * WIDTH) in {
        dims for c in gathers if c["trips"] == LAYERS
        for dims in c["operand_dims"]}
    assert plan["leaves_gathered_in_scan"] == 4
    assert plan["gather_operand_bytes_in_scan"] == \
        (2 * LAYERS + 1) * KERNEL_ELEMENTS // 4 * 2


def test_gathered_together_is_gathered_apart():
    """``GatherPlan.gather(together=True)`` returns what the leaf-by-leaf
    gather returns, to the bit, and its transpose is each leaf's own: the
    same float32 shards of the same sums."""
    from jax.sharding import PartitionSpec as P

    from deepspeed_tpu.utils.compat import shard_map

    mesh = MeshTopology(axis_sizes={"data": 4},
                        devices=jax.devices()[:4]).mesh
    shapes = {"a": (8, 6), "b": (8, 10), "c": (16, 6), "small": (6,)}
    specs = {k: P("data") if len(v) == 2 else P() for k, v in shapes.items()}
    tree = {k: jax.random.normal(jax.random.PRNGKey(i), v, jnp.float32)
            for i, (k, v) in enumerate(shapes.items())}
    plan = zero.GatherPlan(
        mesh, specs, {k: jax.ShapeDtypeStruct(v, jnp.float32)
                      for k, v in shapes.items()}, {"": 0})

    def run(together):
        def local(tree):
            def loss(tree):
                whole = plan.gather(tree, (), dtype=jnp.bfloat16,
                                    together=together)
                return sum((w.astype(jnp.float32) ** 2).sum() * (i + 1)
                           for i, w in enumerate(
                               jax.tree_util.tree_leaves(whole))), whole
            (_, whole), grads = jax.value_and_grad(loss, has_aux=True)(tree)
            return whole, grads
        return jax.jit(shard_map(
            local, mesh=mesh, in_specs=(specs,),
            out_specs=({k: P() for k in shapes}, specs),
            check_vma=False))(tree)

    (whole_a, grads_a), (whole_b, grads_b) = run(False), run(True)
    for k, shape in shapes.items():
        assert whole_b[k].shape == shape
        np.testing.assert_array_equal(whole_a[k], whole_b[k])
        np.testing.assert_allclose(grads_a[k], grads_b[k], rtol=1e-6)
    assert whole_b["a"].dtype == jnp.bfloat16
    assert whole_b["small"].dtype == jnp.float32  # whole already: untouched


@pytest.mark.parametrize("case", ["stage0-on-a-mesh", "stage3-one-device",
                                  "stage3-tp-mesh", "serving-decode"])
def test_without_a_plan_the_scan_carries_activations_alone(case):
    """No plan, no prefetch: every other program's scan is ``nn.scan``'s,
    whose carry is the activations (and a serving program's pools)."""
    if case == "serving-decode":
        cfg = GPT2Config(vocab_size=VOCAB, n_positions=SEQ, n_embd=WIDTH,
                         n_layer=4, n_head=HEADS, dtype=jnp.bfloat16)
        module = GPT2LMHeadModel(cfg.for_paged_decode(9, 8, ""))
        pg = {"block_tables": jnp.zeros((3, 4), jnp.int32),
              "lengths": jnp.zeros((3,), jnp.int32),
              "num_valid": jnp.ones((3,), jnp.int32), "prefill": False}
        variables = jax.eval_shape(lambda: module.init(
            jax.random.PRNGKey(0), jnp.zeros((3, 1), jnp.int32), paging=pg))
        jaxpr = jax.make_jaxpr(lambda v, ids: module.apply(
            v, ids, mutable=["cache"], paging=pg))(
            variables, jax.ShapeDtypeStruct((3, 1), jnp.int32))
    else:
        stage, axes = {"stage0-on-a-mesh": (0, {"data": 4}),
                       "stage3-one-device": (3, {"data": 1}),
                       "stage3-tp-mesh": (3, {"data": 2, "tp": 2})}[case]
        engine = _engine(stage, axes, jnp.bfloat16, layers=4)
        jaxpr = _step_jaxpr(engine, axes)
        assert (engine._zero3_program or {}).get("program") \
            != "gather_at_use"
    scans = _scans(jaxpr)
    assert scans and all(length == 4 for length, *_ in scans)
    for _, _, carry, _ in scans:
        assert not [k for k in KERNELS if k in _shapes(carry)]
    if case == "serving-decode":
        assert "custom_vjp" not in str(jaxpr)


@pytest.mark.parametrize("layers", [1, 2, 5])
def test_stage_3_agrees_with_stage_0_at_every_depth(layers):
    """One layer (nothing to gather ahead), two and five: two float32
    steps agree with stage 0's."""
    axes = {"data": 4}
    e0 = _engine(0, axes, layers=layers)
    l0 = _train(e0, axes)
    p0 = jax.tree_util.tree_map(np.asarray, e0.state.params)
    e3 = _engine(3, axes, layers=layers)
    l3 = _train(e3, axes)
    np.testing.assert_allclose(l3, l0, rtol=2e-6, atol=2e-6)
    for a, b in zip(jax.tree_util.tree_leaves(p0),
                    jax.tree_util.tree_leaves(e3.state.params)):
        # (one weight in 80,000 lands 2.4e-6 off at 5 layers on the
        # parent's program too: the order of float32 sums)
        np.testing.assert_allclose(np.asarray(b), a, rtol=0, atol=5e-6)


@pytest.mark.parametrize("feature", ["dropout", "pld"])
def test_a_layers_randomness_has_a_key_of_its_own(feature):
    """Dropout and progressive layer drop draw from one key a layer
    (split off the step's), on every chip its own: the step trains, two
    steps differ, and ``eval_batch`` draws nothing."""
    axes = {"data": 4}
    kw = {"dropout": 0.1} if feature == "dropout" else {"pld": True}
    engine = _engine(3, axes, jnp.float32, layers=2, **kw)
    losses = _train(engine, axes, steps=3)
    assert all(np.isfinite(losses))
    a = float(engine.eval_batch(_batch(engine, axes, step=9)))
    b = float(engine.eval_batch(_batch(engine, axes, step=9)))
    assert a == b


def test_persistence_is_judged_on_the_layer():
    """``[n_layer, 256]`` is 768 elements stacked and under the threshold
    either way; ``[n_layer, 64, 64]`` is sharded although... and a leaf of
    ``[n_layer, 400]`` (1,200 stacked, 400 a layer) stays whole because
    the unit that is gathered is the layer's slice."""
    mesh = MeshTopology(axis_sizes={"data": 4},
                        devices=jax.devices()[:4]).mesh
    shapes = {"transformer": {"h": {"block": {
        "bias": jax.ShapeDtypeStruct((LAYERS, 400), jnp.float32),
        "kernel": jax.ShapeDtypeStruct((LAYERS, 64, 192), jnp.float32)}}}}
    sites = {"transformer/h/block": 1}
    stacked, _ = zero.build_zero_shardings(
        shapes, mesh, stage=3, persistence_threshold=THRESHOLD)
    by_unit, opt = zero.build_zero_shardings(
        shapes, mesh, stage=3, persistence_threshold=THRESHOLD, sites=sites)
    blk = lambda t: t["transformer"]["h"]["block"]  # noqa: E731
    assert "data" in str(blk(stacked)["bias"].spec)      # the old rule
    assert "data" not in str(blk(by_unit)["bias"].spec)  # persistent
    # a site's leaf is split on the unit's LEADING dim (a gather along it
    # is a concatenation), never on the scanned one; its optimizer state
    # follows
    assert tuple(blk(by_unit)["kernel"].spec) == (None, "data", None)
    assert tuple(blk(opt)["kernel"].spec) == (None, "data", None)
    assert tuple(blk(stacked)["kernel"].spec) == (None, None, "data")


def test_the_seam_is_the_identity_without_a_plan():
    tree = {"w": jnp.ones((4, 4))}
    assert not zero.gathering()
    assert zero.gather_at_use(tree, ("anything",), dtype=jnp.bfloat16,
                              stacked=1) is tree


_COLLECTIVE = re.compile(
    r"all[-_]gather|reduce[-_]scatter|all[-_]to[-_]all|all[-_]reduce|"
    r"collective[-_]permute|manual_computation|shard_map|psum")


@pytest.mark.parametrize("stage", [0, 3])
def test_one_device_lowers_to_no_collective(stage):
    """On one device (any stage) the ZeRO axes multiply to one: no plan,
    no ``shard_map``, no collective in the lowered step."""
    axes = {"data": 1}
    engine = _engine(stage, axes, jnp.bfloat16)
    engine(_batch(engine, axes))
    lowered = engine._jit_fused.lower(
        engine.state, engine._shard_batch(_batch(engine, axes)),
        jnp.float32(0)).as_text()
    assert not _COLLECTIVE.search(lowered)
    assert (engine._zero3_program or {}).get("program") != "gather_at_use"


def test_stage_0_on_a_mesh_keeps_the_gspmd_program():
    axes = {"data": 4}
    engine = _engine(0, axes, jnp.bfloat16)
    engine(_batch(engine, axes))
    lowered = engine._jit_fused.lower(
        engine.state, engine._shard_batch(_batch(engine, axes)),
        jnp.float32(0)).as_text()
    assert not re.search(r"manual_computation|shard_map|all_gather|"
                         r"reduce_scatter", lowered)
    assert engine._zero3_program is None


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_serving_programs_hold_no_collective(program):
    """The serving engine's decode and prefill programs run the same
    ``ScanBlocks`` with no engine and no plan: the seam is the identity."""
    cfg = GPT2Config(vocab_size=VOCAB, n_positions=SEQ, n_embd=WIDTH,
                     n_layer=LAYERS, n_head=HEADS, dtype=jnp.bfloat16)
    module = GPT2LMHeadModel(cfg.for_paged_decode(9, 8, ""))
    n, t = (3, 1) if program == "decode" else (1, 16)
    pg = {"block_tables": jnp.zeros((n, 4), jnp.int32),
          "lengths": jnp.zeros((n,), jnp.int32),
          "num_valid": jnp.ones((n,), jnp.int32),
          "prefill": program == "prefill"}
    variables = jax.eval_shape(lambda: module.init(
        jax.random.PRNGKey(0), jnp.zeros((n, t), jnp.int32), paging=pg))

    def fn(variables, ids):
        out, vars_ = module.apply(variables, ids, mutable=["cache"],
                                  paging=pg)
        return out, vars_["cache"]

    lowered = jax.jit(fn).lower(
        variables, jax.ShapeDtypeStruct((n, t), jnp.int32)).as_text()
    assert not _COLLECTIVE.search(lowered)
    assert "custom_vjp" not in lowered and "optimization_barrier" not in lowered


def test_a_model_without_the_seam_trains_on_the_gspmd_program():
    """``scan_layers=False`` declares no use site: stage 3 still trains,
    on the partitioner's program, and the engine's log says which."""
    import logging

    from deepspeed_tpu.utils.logging import logger as ds_logger

    axes = {"data": 4}
    records = []
    handler = logging.Handler()
    handler.emit = lambda r: records.append(r.getMessage())
    ds_logger.addHandler(handler)
    try:
        engine = _engine(3, axes, jnp.float32, scan=False)
        losses = _train(engine, axes)
    finally:
        ds_logger.removeHandler(handler)
    assert all(np.isfinite(losses))
    assert engine._zero3_program == {"program": "gspmd"}
    assert engine.describe_topology(include_tensors=False)[
        "zero3_program"] == {"program": "gspmd"}
    assert sum("ZeRO-3 step: GSPMD program" in m for m in records) == 1
    e0 = _engine(0, axes, jnp.float32, scan=False)
    np.testing.assert_allclose(losses, _train(e0, axes), rtol=1e-5)


def test_a_tp_mesh_keeps_the_gspmd_program():
    """``lax.all_gather`` under a ``shard_map`` that leaves ``tp`` to the
    partitioner gets its operand whole over ``tp`` (the compiled step
    gathered over ``tp`` first, then ``tp`` times the bytes over ``data``),
    so a mesh with a live ``tp`` axis keeps the partitioner's program,
    where the ``tp`` entry of every spec survives; it trains as stage 0
    on the same mesh does."""
    axes = {"data": 2, "tp": 2}
    engine = _engine(3, axes, jnp.float32)
    losses = _train(engine, axes)
    assert engine._zero3_program == {"program": "gspmd"}
    spec = engine._state_shardings.params["transformer"]["h"]["block"][
        "attn"]["c_attn"]["kernel"].spec
    assert "tp" in str(spec) and "data" in str(spec)
    np.testing.assert_allclose(losses, _train(_engine(0, axes), axes),
                               rtol=1e-5)


def test_the_engine_logs_the_plan_once():
    import logging

    from deepspeed_tpu.utils.logging import logger as ds_logger

    axes = {"data": 4}
    records = []
    handler = logging.Handler()
    handler.emit = lambda r: records.append(r.getMessage())
    ds_logger.addHandler(handler)
    try:
        engine = _engine(3, axes, jnp.bfloat16)
        _train(engine, axes, steps=2)
        engine.eval_batch(_batch(engine, axes))
    finally:
        ds_logger.removeHandler(handler)
    logged = [m for m in records if "ZeRO-3 step:" in m]
    assert len(logged) == 1 and "gather-at-use program over data" in logged[0]
    plan = engine.describe_topology(include_tensors=False)["zero3_program"]
    assert str(plan["gather_operand_bytes_step"]) in logged[0]
    assert str(plan["scatter_operand_bytes_step"]) in logged[0]
    # which leaves the ring scattered, in how many permutes and bytes
    assert plan["leaves_scattered_by_ring"] == 6
    assert (f"{plan['leaves_scattered_by_ring']} leaves by a ring of "
            f"{plan['ring_permutes_step']} float32 permutes "
            f"({plan['ring_operand_bytes_step']} operand bytes)") in logged[0]
    # how many of a step's leaf gathers run a layer ahead of their use:
    # the forward's, all but the first layer's
    assert plan["gathers_ahead_step"] == 4 * (LAYERS - 1)
    assert (f"({plan['gathers_ahead_step']} gathers a step one layer "
            "ahead)") in logged[0]
    # a layer's four kernels: shard (bf16) gathered forward (every layer's
    # and, in the last step, the last layer's once more) and again in the
    # rematerialised backward, whole (f32) scattered once
    kernels = WIDTH * 3 * WIDTH + WIDTH * WIDTH + 2 * WIDTH * 4 * WIDTH
    assert plan["gather_operand_bytes_in_scan"] == \
        (2 * LAYERS + 1) * kernels // 4 * 2
    tables = VOCAB * WIDTH + SEQ * WIDTH
    assert plan["scatter_operand_bytes_step"] == \
        (LAYERS * kernels + tables) * 4


def test_the_fused_step_holds_no_accumulation_buffer():
    """Gradients go from backward to the update inside the fused program:
    the state carries no parameter-sized zero buffer through every step.
    Re-gating to accumulation (``set_train_batch_size``) makes one, sharded
    as the gradients are, and the micro-step path trains on."""
    axes = {"data": 4}
    engine = _engine(3, axes, jnp.float32)
    losses = _train(engine, axes)
    assert engine._fused_step and engine.state.grad_acc == {}
    engine.set_train_batch_size(2 * engine.train_batch_size())
    assert not engine._fused_step
    acc = engine.state.grad_acc["transformer"]["h"]["block"]["mlp"]["c_fc"][
        "kernel"]
    assert acc.shape == (LAYERS, WIDTH, 4 * WIDTH)
    assert acc.sharding.spec == engine._grad_shardings["transformer"]["h"][
        "block"]["mlp"]["c_fc"]["kernel"].spec
    for step in (2, 3):  # two micro-steps, one boundary
        loss = engine(_batch(engine, axes, step=step))
        engine.backward(loss)
        engine.step()
    assert np.isfinite(float(loss)) and float(loss) < losses[0] + 1.0
    assert engine.global_steps == 3
