"""The Phi-4-mini-flash family at a small size on the CPU: the program in
float32 against the plain reference (``perfbench/reference_phi4flash``, the
recurrence ONE position at a time, no cache) on LOGITS: the plain call and
each mixer alone; prefill chunks then decode through ``ServingEngine``'s
pools (the rings, the one shared cache, the state) against the reference's
full forward; a slot's second tenant; the chunk program's one row and what
it counts; what the cross layers own; differential attention at ``lambda``
0; the controls the comparisons are not blind to; and each refusal by
name."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from deepspeed_tpu.models import blocks, phi4flash
from deepspeed_tpu.models.phi4flash import (DiffAttention, GatedMemoryUnit,
                                            Mamba1Mixer, Phi4FlashConfig,
                                            Phi4FlashForCausalLM)
from perfbench import reference_phi4flash as reference
from tests.unit.served_family import REFUSED, Family, highest, prompts  # noqa: F401

# float32 program against the float32 reference, on logits of order 0.5:
# what another order of summation leaves (the two agree to 3e-7 here)
TOL = 3e-6
BLOCK = 4


def shape_of(cfg: Phi4FlashConfig) -> dict:
    """The reference's view of a program config (the family builds the
    same from a configuration file)."""
    return dict(heads=cfg.num_attention_heads,
                kv_heads=cfg.num_key_value_heads, eps=cfg.layer_norm_eps,
                window=cfg.sliding_window, ssm_state=cfg.mamba_d_state,
                kinds=tuple(cfg.kind(i)
                            for i in range(cfg.num_hidden_layers)))


def _stirred(params):
    """The norms' biases, the projections' biases and the sub-layer norms'
    weights away from their initial 0 and 1: a term the program dropped
    would otherwise be invisible; and ``W_x`` eight times its N(0, 0.02):
    over 128 inputs and not 5,120 it gives ``B`` and ``C`` of 0.07, and the
    state's term is then a hundredth of ``D x``."""
    def leaf(path, x):
        name = jax.tree_util.keystr(path)
        if "x_proj" in name:
            return 8.0 * x
        if "bias" in name and "conv" not in name and "dt_" not in name:
            key = jax.random.PRNGKey(len(name) + x.size)
            return 0.1 * jax.random.normal(key, x.shape, x.dtype)
        if "subln" in name:
            key = jax.random.PRNGKey(x.size)
            return x + 0.3 * jax.random.normal(key, x.shape, x.dtype)
        return x
    return jax.tree_util.tree_map_with_path(leaf, params)


# prompts in chunks of 8; a window of 8 = a ring of 3 blocks of 4
FAMILY = Family(Phi4FlashConfig, Phi4FlashForCausalLM, reference, shape_of,
                TOL, perturb=_stirred,
                serving={"decode_slots": 3, "block_size": BLOCK,
                         "max_model_len": 64, "prefill_chunk_tokens": 8})
engines = FAMILY.engines()
make, reference_logits = FAMILY.make, FAMILY.reference_logits


@pytest.fixture
def served():
    cfg, _, params = make()
    return cfg, params, FAMILY.shared_engine(params, cfg)


# ---------------------------------------------------------------------------
# the plain call, each mixer alone
# ---------------------------------------------------------------------------
def test_full_forward_matches_the_reference_at_every_position(highest):
    cfg, module, params = make()
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 37))
    got = np.asarray(FAMILY.plain(cfg)(params, jnp.asarray(ids)))
    assert got.shape == (2, 37, cfg.vocab_size)
    assert np.abs(got - reference_logits(cfg, params, ids)).max() <= TOL
    # tied: no head of its own; LayerNorm: a weight and a bias
    assert "lm_head" not in params
    assert set(params["norm"]) == set(
        params["layers_3_input_layernorm"]) == {"scale", "bias"}
    assert set(params["layers_0_mamba"]) == {
        "in_proj", "conv", "conv_bias", "x_proj", "dt_proj", "dt_bias",
        "A_log", "D", "out_proj"}


def test_the_layer_kinds_are_the_sources():
    """Even layers Mamba-shaped (a Mamba-1 up to the middle, then units),
    odd ones attention (windows, the one full layer, then cross layers): 9
    + 8 + 1 + 7 + 7 at the published depth; the window includes the query;
    ``dt_rank`` = ceil(d / 16), the head 64."""
    cfg = Phi4FlashConfig()
    kinds = [cfg.kind(i) for i in range(32)]
    assert kinds[:4] == ["mamba", "window", "mamba", "window"]
    assert kinds[14:20] == ["mamba", "window", "mamba", "full", "gmu",
                            "cross"]
    assert {k: kinds.count(k) for k in set(kinds)} == {
        "mamba": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}
    assert cfg.cache_readers == 8 and cfg.head_dim == 64
    assert cfg.dt_rank == 160 and cfg.mamba_inner == 5120
    assert cfg.lambda_init(17) == pytest.approx(0.8 - 0.6 * np.exp(-5.1))
    tiny = Phi4FlashConfig.tiny()
    assert [tiny.kind(i) for i in range(8)] == [
        "mamba", "window", "mamba", "window", "mamba", "full", "gmu",
        "cross"]
    model = Phi4FlashForCausalLM(tiny)
    assert [i for i in range(8) if model.rows_from(i)] == [6]


@pytest.mark.parametrize("mixer", ["mamba", "window", "full", "gmu"])
def test_a_mixer_alone_matches_the_reference(highest, mixer):
    cfg, _, params = make()
    shape = shape_of(cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 21, cfg.hidden_size))
    if mixer == "mamba":
        p = params["layers_4_mamba"]
        got, left = Mamba1Mixer(cfg, True).apply({"params": p}, x, pools={})
        want, memory, _ = reference.mamba(x, p, shape)
        assert np.abs(np.asarray(left["memory"] - memory)).max() <= 1e-5
    elif mixer == "gmu":
        p = params["layers_6_gmu"]
        memory = jax.random.normal(jax.random.PRNGKey(3),
                                   (2, 21, cfg.mamba_inner))
        got, _ = GatedMemoryUnit(cfg).apply({"params": p}, x,
                                            {"memory": memory})
        want = reference.gmu(x, p, memory)
    else:
        layer = 1 if mixer == "window" else 5
        p = params[f"layers_{layer}_attn"]
        got, _ = DiffAttention(cfg, mixer, layer).apply({"params": p}, x,
                                                        pools={})
        want, _ = reference.attention(x, p, shape,
                                      reference.lambda_init(layer), mixer)
    scale = float(np.abs(np.asarray(want)).max())
    assert np.abs(np.asarray(got - want)).max() <= 1e-5 * scale and scale > 0


def test_the_cross_layers_own_no_key_value_or_pool(highest, served):
    """A cross layer has a query and an output projection and the four
    lambda vectors; the global pools have ONE layer; what a token keeps
    (``kv_bytes_per_token``) and what a step reads of it part."""
    cfg, params, srv = served
    assert set(params["layers_7_attn"]) == {
        "q_proj", "o_proj", "subln", "lambda_q1", "lambda_k1", "lambda_q2",
        "lambda_k2"}
    assert "qkv_proj" in params["layers_5_attn"]
    assert set(params["layers_6_gmu"]) == {"in_proj", "out_proj"}
    lanes = cfg.num_key_value_heads * cfg.head_dim
    shapes = {k: v.shape for k, v in srv.cache.items()}
    assert shapes["global_key_pool"] == shapes["global_value_pool"] == (
        1, srv.num_blocks, BLOCK, lanes)
    assert shapes["window_key_pool"] == (2, 1 + 3 * 3, BLOCK, lanes)
    assert shapes["ssm_state_pool"] == (3, 1 + 3, 1, cfg.mamba_d_state, 128)
    assert srv.cache["ssm_state_pool"].dtype == jnp.float32
    assert shapes["ssm_conv_pool"] == (3, 1 + 3, 3 * cfg.mamba_inner)
    dcfg = srv._dmodule.config
    kept = dcfg.kv_bytes_per_token()
    assert kept == {"global": 2 * lanes * 4, "window": 2 * 2 * lanes * 4}
    live = np.asarray([5, 30], np.int64)
    read = dcfg.kv_live_bytes(live)
    # the one pool once for each of the two layers that read it; a ring
    # holds 12 rows; the state its fixed size a busy slot
    assert read["global"] == 35 * kept["global"] * 2
    assert read["window"] == (5 + 12) * kept["window"]
    assert read["state"] == 2 * dcfg.state_bytes_per_slot()
    assert Phi4FlashConfig().kv_bytes_per_token()["global"] == 5120
    assert Phi4FlashConfig().state_bytes_per_slot() == 9 * (327680 + 30720)


def test_lambda_zero_and_a_unit_norm_give_grouped_attention_over_pairs(
        highest):
    """With ``lambda`` forced to 0 the second softmax drops out, and with
    the sub-layer norm's weight 1 what is left is plain grouped attention
    of the ``q1`` heads over ``k1`` with values ``[v1 | v2]``, normed."""
    cfg, _, params = make()
    p = dict(params["layers_5_attn"])
    init = cfg.lambda_init(5)
    # exp(lq1 . lk1) - exp(lq2 . lk2) + init = 0
    dh = cfg.head_dim
    p["lambda_q1"] = p["lambda_k1"] = jnp.zeros((dh,))
    p["lambda_q2"] = jnp.full((dh,), 1.0)
    p["lambda_k2"] = jnp.full((dh,), float(np.log(1.0 + init)) / dh)
    p["subln"] = {"scale": jnp.ones((2 * dh,))}
    x = jax.random.normal(jax.random.PRNGKey(2), (1, 13, cfg.hidden_size))
    got, _ = DiffAttention(cfg, "full", 5).apply({"params": p}, x, pools={})
    q, k, v = reference.projected(x, p, shape_of(cfg))
    heads, kv = cfg.num_attention_heads, cfg.num_key_value_heads
    q1 = q.reshape(1, 13, heads // 2, 2, dh)[:, :, :, 0]
    k1 = k.reshape(1, 13, kv // 2, 2, dh)[:, :, :, 0]
    pairs = v.reshape(1, 13, kv // 2, 2 * dh)
    o = blocks.causal_gqa(q1, k1, pairs)            # [1, 13, H / 2, 2 dh]
    o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + 1e-5)
    want = reference._linear((o * (1 - init)).reshape(1, 13, -1),
                             p["o_proj"])
    assert np.abs(np.asarray(got - want)).max() <= 1e-5


def test_bf16_fails_the_float32_tolerance():
    cfg, _, params = make()
    ids = np.random.default_rng(1).integers(0, cfg.vocab_size, (2, 37))
    low = FAMILY.plain(dataclasses.replace(cfg, dtype=jnp.bfloat16))
    got = np.asarray(low(params, jnp.asarray(ids)))
    assert np.abs(got - reference_logits(cfg, params, ids)).max() > 100 * TOL


def test_the_initialisers_are_mamba1s_own():
    """``A[c, n] = -(n + 1)``, ``delta`` at ``dt = 0`` log-uniform in
    [0.001, 0.1], ``D`` = 1, taps in [-1/2, 1/2], the lambda vectors N(0,
    0.1)."""
    cfg = Phi4FlashConfig.tiny(hidden_size=256, num_attention_heads=8)
    params = jax.jit(Phi4FlashForCausalLM(cfg).init)(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
    p = params["layers_0_mamba"]
    assert np.allclose(np.exp(np.asarray(p["A_log"])),
                       np.arange(1, cfg.mamba_d_state + 1)[None])
    delta = np.log1p(np.exp(np.asarray(p["dt_bias"])))
    assert 1e-3 <= delta.min() * 1.001 and delta.max() <= 0.1001
    assert np.log(delta).std() > 0.8 and (np.asarray(p["D"]) == 1).all()
    taps = np.asarray(p["conv"])
    assert np.abs(taps).max() <= 0.5 and taps.std() > 0.2
    assert 0.05 < np.asarray(params["layers_1_attn"]["lambda_q1"]).std() < 0.2


# ---------------------------------------------------------------------------
# through the rings, the shared cache and the per-slot state
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk", [0, 8], ids=["whole-prompt", "chunked"])
def test_paged_logits_match_the_reference(highest, served, chunk):
    """Prefill then decode through the pools against the reference's ONE
    full forward pass, on LOGITS: a prompt of 27 (longer than the window of
    8 and than a chunk) in a bucket of 40, or in chunks of 8 (each past the
    first starts from the stored state and the ring, the last holds 3 real
    positions); every call of ``T > 1`` hands back its last real row alone,
    then 14 decode steps."""
    cfg, params, srv = served
    assert FAMILY.paged_logits_match(srv, cfg, params, prompts(cfg, [27])[0],
                                     14, chunk=chunk) <= TOL
    assert FAMILY.positions[:4] == ([7, 15, 23, 26] if chunk
                                    else [26, 27, 28, 29])


def test_a_slots_second_tenant_does_not_see_the_firsts_state(highest, served):
    """Requests one after the other in ONE slot, the second shorter than
    the first: each is the reference's (the state restarts, the ring's
    older laps are masked by position)."""
    cfg, params, srv = served
    for prompt in prompts(cfg, [30, 7]):
        assert FAMILY.paged_logits_match(srv, cfg, params, prompt, 5, slot=2,
                                         chunk=8) <= TOL, len(prompt)


def test_the_chunk_programs_one_row_is_the_plain_calls_row(highest, served):
    """A paged call of ``T`` = 8 hands back ``[1, 1, vocab]``: the row at
    ``num_valid - 1`` of the plain call over the same tokens; and counts
    one cross row for its real tokens."""
    cfg, params, srv = served
    prompt = prompts(cfg, [13])[0]
    got, _ = FAMILY.paged_logits(srv, prompt, 0, chunk=8)
    want = np.asarray(FAMILY.plain(cfg)(params, jnp.asarray([prompt])))[0]
    assert got.shape == (2, cfg.vocab_size)
    assert FAMILY.positions == [7, 12]
    assert np.abs(got - want[[7, 12]]).max() <= TOL
    dm = srv._dmodule
    ids = np.zeros((1, 8), np.int32)
    ids[0, :5] = prompt[:5]
    table = srv._slot_table(0, np.arange(1, 17))
    (logits, aux), _ = jax.jit(lambda p, cache, ids, tables: dm.apply(
        {"params": p, "cache": cache}, ids, mutable=["cache"],
        paging={"block_tables": tables,
                "lengths": jnp.zeros((1,), jnp.int32),
                "num_valid": jnp.asarray([5], jnp.int32),
                "prefill": False}))(params, srv.cache, jnp.asarray(ids),
                                    jnp.asarray(table[None]))
    assert logits.shape == (1, 1, cfg.vocab_size)
    assert np.abs(np.asarray(logits)[0, 0] - want[4]).max() <= TOL
    assert type(dm).serve_counters[-2:] == ("cross_rows", "self_tokens")
    assert np.asarray(aux["counters"])[-2:].tolist() == [1, 5]


def test_prefill_chunks_and_decode_through_the_engine(highest, served):
    """Prompts in chunks of 8 (5 requests over 3 slots: slots reused after
    a finish, rows of unequal length) through ``init_inference`` ->
    ``ServingEngine``: every served token the reference's argmax at its
    position; and the engine's counters: what the cut saved, and the bytes
    a step reads by kind."""
    cfg, params, _ = served
    lengths = [5, 19, 33, 9, 26]
    stats, reqs = FAMILY.served_logits_match(
        cfg, params, list(zip(prompts(cfg, lengths), [30, 12, 20, 25, 8])))
    assert max(r.prefill_chunks for r in reqs) == 5
    assert len({r.slot for r in reqs}) == 3
    counted = stats["model_counters"]
    chunks = sum(-(-n // 8) for n in lengths)
    assert counted["prefill"]["self_tokens"] == sum(lengths)
    assert counted["prefill"]["cross_rows"] == chunks
    assert (counted["decode"]["cross_rows"]
            == counted["decode"]["self_tokens"] == stats["busy_slot_steps"])
    assert counted["decode"]["pairs_all"] == 0       # no layer is sparse
    kv = stats["kv_live_bytes"]
    dcfg = FAMILY.shared_engine(params, cfg)._dmodule.config
    assert set(kv) == {"global", "window", "state"}
    assert kv["state"] == (stats["busy_slot_steps"]
                           * dcfg.state_bytes_per_slot())
    assert 0 < kv["window"] < kv["global"]
    assert {"phi4_ssm_prefill_chunk", "phi4_ssm_decode",
            "phi4_window_cached_xla", "phi4_global_cached_tiled_xla",
            "phi4_cross_cached_xla"} <= set(stats["attention_paths"])


def test_the_slots_seam_is_a_ring_and_a_state_row_under_one_knob(highest):
    """``paged_slot_state_for``: ``entries`` = the ring's blocks and one,
    in two ``parts`` that each count their own pool from 1; the pools that
    belong to a slot do not grow with the context."""
    cfg, _, params = make()
    seam = cfg.paged_slot_state_for(BLOCK)
    assert seam["entries"] == 4 and seam["parts"] == (3, 1)
    assert seam["knob"] == "state_slots"
    sizes = {}
    for longest in (32, 64):
        srv = FAMILY.serving_engine(params, cfg, max_model_len=longest)
        sizes[longest] = {k: v.shape for k, v in srv.cache.items()}
        table = srv._slot_table(2, np.arange(3))
        srv.destroy()
    assert table.tolist() == [0, 1, 2, 7, 8, 9, 3]
    for name in ("window_key_pool", "ssm_state_pool", "ssm_conv_pool"):
        assert sizes[32][name] == sizes[64][name]
    assert sizes[32]["global_key_pool"][1] < sizes[64]["global_key_pool"][1]
    # a family with one kind of state a slot says no parts
    from deepspeed_tpu.models.granite_hybrid import GraniteHybridConfig
    assert "parts" not in GraniteHybridConfig.tiny().paged_slot_state_for(4)


def _other_slots_blocks(cfg, q, paging, table, *rest):
    """Control: the cross layers read the blocks of the slot before."""
    return _cross_attend(cfg, q, paging, jnp.roll(table, 1, axis=0), *rest)


_cross_attend = phi4flash.cross_attend


def test_controls_move_the_logits(highest, monkeypatch, served):
    """What the comparisons above are not blind to: a state not carried
    between calls, and cross layers that read another row's blocks."""
    cfg, params, srv = served
    long, short = prompts(cfg, [30, 7])
    with monkeypatch.context() as m:
        m.setattr(phi4flash.mamba1_scan, "mamba1_chunk_scan",
                  lambda x, d, a, b, c, state: _scan(
                      x, d, a, b, c, jnp.zeros_like(state)))
        assert FAMILY.paged_logits_match(srv, cfg, params, long, 3, slot=2,
                                         chunk=8, retrace=True) > 100 * TOL
    # decode steps whose cross layers read the row before's blocks (an
    # idle slot's: the garbage block)
    with monkeypatch.context() as m:
        m.setattr(phi4flash, "cross_attend", _other_slots_blocks)
        assert FAMILY.paged_logits_match(srv, cfg, params, long, 3, slot=2,
                                         chunk=8, retrace=True) > 100 * TOL
    # (and the engine's own programs were traced before either patch)
    assert FAMILY.paged_logits_match(srv, cfg, params, long, 3, slot=2,
                                     chunk=8, retrace=True) <= TOL


_scan = phi4flash.mamba1_scan.mamba1_chunk_scan


def test_decode_through_the_kernels_matches_the_xla_paths(monkeypatch):
    """The decode program with the Pallas kernels in it (interpret mode):
    the state update on the pool in place, the hybrid kernel over the ring
    and over the one shared pool with a head's VALUE GROUP of two KV heads,
    beside idle slots, against the same steps on the XLA paths."""
    cfg, _, params = make()
    got, want, paths = FAMILY.decode_through_the_kernels(
        monkeypatch, cfg, params, prompts(cfg, [19])[0], 3, chunk=8,
        experts=False)
    assert paths.get("phi4_window_decode_kernel") and paths.get(
        "phi4_global_decode_kernel") and paths.get("phi4_cross_decode_kernel")
    assert np.abs(got - want).max() <= TOL


def test_the_family_is_a_client_of_the_shared_blocks():
    """It imports no other family; ``blocks.py`` does not name it; the
    config's ``for_paged_decode`` and the module's ``__call__`` are the
    shared ones; the shell's new attributes leave the other families'
    defaults."""
    import ast
    import pathlib

    models = pathlib.Path(phi4flash.__file__).parent
    tree = ast.parse((models / "phi4flash.py").read_text())
    imported = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            imported |= {f"{node.module}.{a.name}" for a in node.names}
    assert "deepspeed_tpu.models.blocks" in imported
    assert not [m for m in imported
                for other in ("mimo_v2", "lfm2_moe", "deepseek_v2", "llama",
                              "gpt2", "granite_hybrid", "exaone_moe",
                              "bailing_hybrid", "deepseek_v32")
                if m.startswith(f"deepspeed_tpu.models.{other}")]
    assert "phi4" not in (models / "blocks.py").read_text().lower()
    assert (Phi4FlashConfig.for_paged_decode
            is blocks.ServedConfig.for_paged_decode)
    assert Phi4FlashForCausalLM.__call__ is blocks.PagedDecoder.__call__
    shell = blocks.PagedDecoder
    assert shell.norm_class is blocks.RMSNorm and not shell.carries
    assert not shell.rows_from(None, 3)
    assert Phi4FlashForCausalLM.norm_class is blocks.LayerNorm
    assert blocks.value_groups(jnp.ones((1, 2, 4, 8)), 1).shape == (1, 2, 4,
                                                                    8)
    v = jnp.arange(16.0).reshape(1, 1, 4, 4)
    wide = np.asarray(blocks.value_groups(v, 2))
    assert wide.shape == (1, 1, 4, 8)
    assert (wide[0, 0, 0] == wide[0, 0, 1]).all() and (
        wide[0, 0, 2] == np.arange(8, 16)).all()


# ---------------------------------------------------------------------------
# refusals, by name
# ---------------------------------------------------------------------------
@REFUSED
def test_mechanisms_that_know_block_tables_only_refuse_the_model(serving,
                                                                 mechanism):
    said = FAMILY.mechanism_refusal(serving, mechanism)
    assert "a ring a decode slot" in said and "a state of fixed size" in said
    assert "share the one cache" in said


def test_tensor_parallel_refuses_the_model():
    assert "eight layers share" in FAMILY.tensor_parallel_refusal()


def test_migration_refuses_the_model():
    assert all("state of fixed size" in said
               for said in FAMILY.migration_refusals())


def test_the_config_refuses_what_the_family_does_not_implement():
    cfg = Phi4FlashConfig.tiny()
    with pytest.raises(ValueError, match="state_slots"):
        cfg.for_paged_decode(9, 4)
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        cfg.for_paged_decode(9, 4, kv_dtype="int8", state_slots=2)
    with pytest.raises(ValueError, match="mb_per_layer"):
        Phi4FlashConfig.tiny(mb_per_layer=4)
    with pytest.raises(ValueError, match="pairs adjacent heads"):
        Phi4FlashConfig.tiny(num_key_value_heads=1)
    cfg, _, params = make()
    with pytest.raises(Exception, match="routed_experts_kept"):
        FAMILY.serving_engine(params, cfg, routed_experts_kept=4)
