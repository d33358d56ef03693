"""The DeepSeek-V3.2 family at a small size on the CPU: the program in
float32 against the plain reference (``perfbench/reference_deepseek_v32.py``)
on LOGITS: the plain call; prefill in chunks then decode through BOTH pools,
at a tiny ``index_topk`` that the context passes several times over; with
``index_topk`` at least the context, the model equal to the same weights
attended DENSELY; the indexer's two rotations against a plain
re-implementation; the chunk kernel against the XLA path; the two kinds of
live bytes and the two counters in the engine's ledger; the chosen keys
handed back for a request that asks; and the mechanisms that refuse the
model by name. (The controls the comparisons are not blind to go through
the cell's own check: ``tests/perfbench/test_deepseek_v32_cell.py``.)"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.models import deepseek_v32
from deepspeed_tpu.models.deepseek_v2 import yarn_frequencies
from deepspeed_tpu.models.deepseek_v32 import (DeepseekV32Config,
                                               DeepseekV32ForCausalLM,
                                               SparseLatentAttention)
from deepspeed_tpu.ops import dsa_index_select as select_op
from deepspeed_tpu.ops import dsa_sparse_attend as attend_op
from deepspeed_tpu.parallel.topology import reset_topology
from deepspeed_tpu.serving import ServingEngine
from perfbench import reference_deepseek_v32 as reference
from tests.unit.served_family import REFUSED, Family, highest  # noqa: F401

TOL = 1e-4
BLOCK = 4


def shape_of(cfg: DeepseekV32Config, first_expert: int = 0) -> dict:
    """The reference's view of a program config (the family builds the
    same from a configuration file)."""
    return dict(layers=cfg.num_hidden_layers, heads=cfg.num_attention_heads,
                nope=cfg.qk_nope_head_dim, rope=cfg.qk_rope_head_dim,
                v_dim=cfg.v_head_dim, rank=cfg.kv_lora_rank,
                eps=cfg.rms_norm_eps, rope_theta=cfg.rope_theta,
                yarn=dataclasses.asdict(cfg.rope_scaling),
                index_heads=cfg.index_n_heads, index_dim=cfg.index_head_dim,
                index_topk=cfg.index_topk, top_k=cfg.num_experts_per_tok,
                n_group=cfg.n_group, topk_group=cfg.topk_group,
                route_scale=cfg.routed_scaling_factor,
                dense=cfg.first_k_dense_replace, first_expert=first_expert)


FAMILY = Family(DeepseekV32Config, DeepseekV32ForCausalLM, reference,
                shape_of, TOL,
                serving={"decode_slots": 3, "block_size": BLOCK,
                         "max_model_len": 96},
                aux_of=lambda aux: aux["selected"])
engines = FAMILY.engines()
make = FAMILY.make


def _programs(with_layers=False):
    """The plain call and the reference, each ONE jitted program."""
    cfg, _, _ = make()
    return FAMILY.plain(cfg), FAMILY.reference_program(
        cfg, **({"with_layers": True} if with_layers else {}))


def _ids(cfg, rows, length, seed=5):
    return np.random.default_rng(seed).integers(
        0, cfg.vocab_size, (rows, length)).astype(np.int32)


# ---------------------------------------------------------------------------
# the plain call
# ---------------------------------------------------------------------------
def test_full_forward_matches_the_reference(highest):
    """80 positions at ``index_topk`` 16: every query past the sixteenth
    attends a set the indexer chose, five times over by the end."""
    cfg, module, params = make()
    ids = _ids(cfg, 2, 80)
    plain, ref = _programs(True)
    got = plain(params, ids)
    want, seen = ref(params, jnp.asarray(ids))
    assert np.abs(np.asarray(got - want)).max() <= TOL
    chosen = np.asarray(select_op.unpack_bits(seen["selected"], 80))
    assert (chosen.sum(-1)[0, :, 0] == np.minimum(np.arange(80) + 1,
                                                  16)).all()
    assert not chosen[0, 70, 1, 71:].any()              # causal


def test_the_selection_moves_the_logits_and_a_wide_one_is_dense(highest):
    """With ``index_topk`` at least the context every key is chosen and the
    layer is the same weights attended densely: the mixer alone against a
    plain causal softmax over every key, written out here."""
    cfg, module, params = make()
    wide = dataclasses.replace(cfg, index_topk=4096)
    ids = _ids(cfg, 1, 48)
    sparse = _programs()[0](params, ids)
    dense = jax.jit(DeepseekV32ForCausalLM(wide).apply)({"params": params},
                                                        ids)
    assert np.abs(np.asarray(sparse - dense)).max() > 100 * TOL
    assert np.abs(np.asarray(sparse - dense))[0, :16].max() <= TOL
    # the mixer, densely
    p = params["layers_1_attn"]
    x = jax.random.normal(jax.random.PRNGKey(3), (1, 40, cfg.hidden_size))
    got = jax.jit(SparseLatentAttention(wide).apply)({"params": p}, x)[0]
    heads, nope, rope, dv, rank = (cfg.num_attention_heads,
                                   cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                                   cfg.v_head_dim, cfg.kv_lora_rank)
    pos = jnp.arange(40)
    sh = shape_of(cfg)

    def rms(v, scale):
        return v * jax.lax.rsqrt(jnp.mean(v * v, -1, keepdims=True)
                                 + cfg.rms_norm_eps) * scale

    c_q = rms(x[0] @ p["q_a_proj"]["kernel"], p["q_a_layernorm"]["scale"])
    q = (c_q @ p["q_b_proj"]["kernel"]).reshape(40, heads, nope + rope)
    kva = x[0] @ p["kv_a_proj_with_mqa"]["kernel"]
    c = rms(kva[:, :rank], p["kv_a_layernorm"]["scale"])
    kv = (c @ p["kv_b_proj"]).reshape(40, heads, nope + dv)
    q_pe = reference.rotate_pairs(q[..., nope:], pos, sh)
    k_pe = reference.rotate_pairs(kva[:, rank:], pos, sh)
    s = (jnp.einsum("thd,shd->hts", q[..., :nope], kv[..., :nope])
         + jnp.einsum("thr,sr->hts", q_pe, k_pe)) * cfg.softmax_scale
    s = jnp.where(pos[None, :] <= pos[:, None], s, -jnp.inf)
    o = jnp.einsum("hts,shd->thd", jax.nn.softmax(s, -1), kv[..., nope:])
    want = o.reshape(40, -1) @ p["o_proj"]["kernel"]
    assert np.abs(np.asarray(got[0] - want)).max() <= TOL


def test_the_indexers_rotations_are_the_published_ones():
    """The main path rotates INTERLEAVED pairs ``(x_2i, x_2i+1)``; the
    indexer rotates its FIRST ``qk_rope_head_dim`` values BY HALVES ``(x_i,
    x_{i + rope/2})`` and leaves the rest. Against complex multiplication,
    written out as the published code does it."""
    cfg = DeepseekV32Config.tiny()
    rope = cfg.qk_rope_head_dim
    inv, factor = yarn_frequencies(rope, cfg.rope_theta, cfg.rope_scaling)
    assert factor == 1.0                              # mscale = mscale_all_dim
    rng = np.random.default_rng(0)
    x = rng.standard_normal((2, 5, 3, cfg.index_head_dim)).astype(np.float32)
    pos = np.asarray([[0, 1, 2, 3, 4], [7, 8, 9, 100, 101]])
    turn = np.exp(1j * pos[..., None] * np.asarray(inv)[None, None])
    got = np.asarray(deepseek_v32.rotate_first_halves(
        jnp.asarray(x), jnp.asarray(pos), cfg))
    halves = (x[..., :rope // 2] + 1j * x[..., rope // 2:rope]) * turn[
        :, :, None]
    assert np.abs(got[..., :rope // 2] - halves.real).max() < 1e-5
    assert np.abs(got[..., rope // 2:rope] - halves.imag).max() < 1e-5
    assert (got[..., rope:] == x[..., rope:]).all()
    # the reference's own is the same function
    for row in range(2):
        ref = reference.rotate_first_halves(jnp.asarray(x[row]),
                                            jnp.asarray(pos[row]),
                                            shape_of(cfg))
        assert np.abs(np.asarray(ref) - got[row]).max() < 1e-6
    # interleaved pairs, for contrast: another function of the same values
    y = x[..., :rope]
    pairs = (y[..., 0::2] + 1j * y[..., 1::2]) * turn[:, :, None]
    main = np.asarray(deepseek_v32.rotate_pairs(
        jnp.asarray(y), jnp.asarray(pos), cfg))
    assert np.abs(main[..., :rope // 2] - pairs.real).max() < 1e-5
    assert np.abs(main[..., rope // 2:] - pairs.imag).max() < 1e-5
    assert np.abs(main - got[..., :rope]).max() > 0.1


# ---------------------------------------------------------------------------
# through the two pools
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("chunk", [24, 0], ids=["chunks-of-24", "whole"])
def test_paged_logits_match_the_reference(highest, chunk):
    """A prompt of 61 tokens (chunks that do not divide it, a boundary
    inside a block) and 12 decode steps through both pools against the
    reference's ONE full forward pass over the same 73 tokens: logits, and
    the chosen keys of every query of every layer."""
    cfg, _, params = make()
    prompt = _ids(cfg, 1, 61)[0].tolist()
    got, ids, chosen = FAMILY.paged_logits(FAMILY.shared_engine(params, cfg),
                                           prompt, 12, chunk=chunk)
    want, seen = _programs(True)[1](params, jnp.asarray([ids]))
    assert np.abs(got - np.asarray(want)[0, :len(got)]).max() <= TOL
    n = len(got)
    theirs = np.asarray(select_op.unpack_bits(seen["selected"], n))
    mine = np.asarray(select_op.unpack_bits(jnp.asarray(chosen), n))
    assert (mine == theirs[0, :n]).all()
    assert (mine.sum(-1) == np.minimum(np.arange(n) + 1, 16)[:, None]).all()


def test_a_pool_full_of_nan_outside_the_live_prefixes_stays_outside(highest):
    cfg, _, params = make()
    srv = FAMILY.shared_engine(params, cfg)
    was = srv.cache                # put back at the end: the engine is shared
    try:
        srv.cache = jax.tree_util.tree_map(
            lambda pool: jnp.full_like(pool, jnp.nan), srv.cache)
        prompt = _ids(cfg, 1, 37)[0].tolist()
        got, ids, _ = FAMILY.paged_logits(srv, prompt, 6, chunk=16)
        want = _programs()[1](params, jnp.asarray([ids]))
        assert np.isfinite(got).all()
        assert np.abs(got - np.asarray(want)[0, :len(got)]).max() <= TOL
    finally:
        srv.cache = was


def test_the_chunk_kernel_is_the_xla_path(highest, monkeypatch):
    """The masked chunk attention on the Pallas kernel (the interpreter
    here) against the XLA tiles, at widths the kernel takes: whole
    registers, eight heads a group."""
    from deepspeed_tpu.ops import attention
    from deepspeed_tpu.utils.compat import tpu_interpret_mode

    cfg = DeepseekV32Config.tiny(
        dtype=jnp.float32, param_dtype=jnp.float32, num_attention_heads=8,
        qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
        kv_lora_rank=128, num_hidden_layers=1, first_k_dense_replace=1,
        index_topk=96)
    dcfg = cfg.for_paged_decode(1 + 2 * 20, 32)
    mixer = SparseLatentAttention(dcfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 128, cfg.hidden_size))
    lead = (1, 41, 32)
    pools = {"latent_pool": jax.random.normal(
                 jax.random.PRNGKey(2), lead + (cfg.latent_lanes,)),
             "index_pool": jax.random.normal(
                 jax.random.PRNGKey(3), lead + (cfg.index_head_dim,))}
    paging = {"block_tables": jnp.arange(1, 17, dtype=jnp.int32)[None],
              "lengths": jnp.asarray([300], jnp.int32),
              "num_valid": jnp.asarray([128], jnp.int32), "prefill": False}
    # (``paging`` holds a Python bool: closed over, not traced)
    params = jax.jit(lambda x, pools: mixer.init(
        jax.random.PRNGKey(0), x, paging, pools, 0))(x, pools)["params"]
    apply = lambda p, x, pools: mixer.apply({"params": p}, x, paging, pools,
                                            0)
    plain, _, seen = jax.jit(apply)(params, x, pools)
    before = attention.dispatch_counts().get(
        "dsa_chunk_masked_decompressed_kernel", 0)
    monkeypatch.setattr(attention, "_FORCE_DECODE_KERNEL", True)
    with tpu_interpret_mode():
        # (a jit of its own: traced under the patch)
        kernel, _, seen_k = jax.block_until_ready(
            jax.jit(lambda *a: apply(*a))(params, x, pools))
    assert attention.dispatch_counts()[
        "dsa_chunk_masked_decompressed_kernel"] == before + 1
    assert (np.asarray(seen[0]) == np.asarray(seen_k[0])).all()
    assert int(seen[2].min()) == 96
    assert np.abs(np.asarray(kernel - plain)).max() <= TOL
    assert not attend_op.kernel_serves(120, 8, 128, 64, 128, 128, 256, 512)


# ---------------------------------------------------------------------------
# the engine
# ---------------------------------------------------------------------------
def test_the_engine_serves_chunks_counts_and_hands_back_the_chosen_keys(
        highest):
    cfg, module, params = make()
    srv = FAMILY.serving_engine(params, cfg, prefill_chunk_tokens=16,
                                routed_experts_kept=4)
    try:
        prompts = [_ids(cfg, 1, n, seed=n)[0].tolist() for n in (50, 41, 33)]
        reqs = [srv.submit(p, max_new_tokens=9, keep_selected=(i != 1))
                for i, p in enumerate(prompts)]
        while any(r.finish_reason is None for r in reqs):
            srv.step()
        stats = srv.stats()
        # greedy: every served token the reference's argmax behind the
        # tokens before it (one pass over the three, padded to one length:
        # causal, so the padding is unseen); its chosen keys alongside
        seqs = np.zeros((3, 50 + 8), np.int32)
        for row, (prompt, req) in enumerate(zip(prompts, reqs)):
            seqs[row, :len(prompt) + 8] = prompt + list(req.tokens)[:-1]
        want, seen = _programs(True)[1](params, jnp.asarray(seqs))
        for row, (prompt, req) in enumerate(zip(prompts, reqs)):
            assert np.asarray(jnp.argmax(want[row, len(prompt) - 1:
                                              len(prompt) + 8], -1)
                              ).tolist() == list(req.tokens)
        # the chosen keys: only for a request that asked
        assert srv.selected_keys(reqs[1].request_id) is None
        for row in (0, 2):
            sets, n = srv.selected_keys(reqs[row].request_id), len(
                prompts[row]) + 8
            assert sets.shape[:2] == (n, cfg.num_hidden_layers)
            assert sets.dtype == np.uint32
            assert (np.asarray(select_op.unpack_bits(jnp.asarray(sets), n))
                    == np.asarray(select_op.unpack_bits(
                        seen["selected"][row], 58))[:n, :, :n]).all()
        # both kinds of live bytes, and the two counters
        live = stats["kv_live_bytes"]
        assert set(live) == {"latent", "index"} and min(live.values()) > 0
        per = srv._dmodule.config.kv_bytes_per_token()
        assert per == {"latent": 2 * 136 * 4, "index": 2 * 128 * 4}
        for kind in ("prefill", "decode"):
            counted = stats["model_counters"][kind]
            assert 0 < counted["dsa_keys_selected"] < counted["dsa_keys_live"]
        # a decode step's queries each chose exactly index_topk keys a layer
        decode = stats["model_counters"]["decode"]
        assert decode["dsa_keys_selected"] == (
            2 * 16 * stats["busy_slot_steps"])
        assert {"dsa_chunk_masked_decompressed_xla", "dsa_select_passes_xla",
                "dsa_decode_absorbed_gathered_xla"} <= set(
                    stats["attention_paths"])
    finally:
        srv.destroy()


def test_the_published_rows_and_what_a_step_reads():
    cfg = DeepseekV32Config(num_hidden_layers=5)
    assert (cfg.latent_row, cfg.latent_lanes) == (576, 640)
    assert cfg.kv_bytes_per_token() == {"latent": 5 * 1152, "index": 5 * 256}
    # a row of 100 tokens is read whole; of 30,000 the index rows whole and
    # 2,048 latent rows
    assert cfg.kv_live_bytes(np.asarray([100, 30_000])) == {
        "latent": (100 + 2_048) * 5 * 1152, "index": 30_100 * 5 * 256}
    assert abs(cfg.softmax_scale - 192 ** -0.5 * (0.1 * np.log(40.0) + 1) ** 2) < 1e-6
    assert cfg.index_scale == 64 ** -0.5 * 128 ** -0.5
    assert DeepseekV32ForCausalLM.serve_counters[-2:] == (
        "dsa_keys_live", "dsa_keys_selected")
    pools = DeepseekV32ForCausalLM(cfg).pool_shapes(16385, 32)
    assert pools == {"latent_pool": (5, 16385, 32, 640),
                     "index_pool": (5, 16385, 32, 128)}


# ---------------------------------------------------------------------------
# refusals, by name
# ---------------------------------------------------------------------------
@REFUSED
def test_mechanisms_that_read_rows_by_heads_refuse_the_model(serving,
                                                             mechanism):
    said = FAMILY.mechanism_refusal(serving, mechanism)
    assert "one latent row a token" in said
    assert "an index row beside it" in said
    assert "keys and values by heads" in said


def test_tensor_parallel_refuses_the_model():
    said = FAMILY.tensor_parallel_refusal()
    assert "DeepseekV32ForCausalLM" in said and "latent row" in said


def test_migration_refuses_the_model():
    assert all("latent row" in said for said in FAMILY.migration_refusals())


def test_a_model_without_chosen_keys_refuses_a_request_that_asks():
    from deepspeed_tpu.models.deepseek_v2 import (DeepseekV2Config,
                                                  DeepseekV2ForCausalLM)

    cfg = DeepseekV2Config.tiny(dtype=jnp.float32)
    module = DeepseekV2ForCausalLM(cfg)
    params = jax.jit(module.init)(jax.random.PRNGKey(0),
                                  jnp.zeros((1, 8), jnp.int32))["params"]
    reset_topology()
    srv = ServingEngine(deepspeed_tpu.init_inference(
        module, params=params, dtype=cfg.dtype,
        serving={"decode_slots": 2, "block_size": BLOCK,
                 "max_model_len": 32}))
    try:
        with pytest.raises(ValueError, match="keep_selected") as e:
            srv.submit([1, 2, 3], max_new_tokens=2, keep_selected=True)
        assert "return no selected keys" in str(e.value)
        assert srv.submit([1, 2, 3], max_new_tokens=2).state == "queued"
    finally:
        srv.destroy()


def test_for_paged_decode_refuses_what_it_cannot_size():
    cfg = DeepseekV32Config.tiny()
    with pytest.raises(ValueError, match="kv_cache_dtype"):
        cfg.for_paged_decode(9, 4, kv_dtype="int8")
    with pytest.raises(ValueError, match="experts over"):
        DeepseekV32Config.tiny(ep_size=5)
    with pytest.raises(ValueError, match="rotates pairs"):
        DeepseekV32Config.tiny(qk_rope_head_dim=7)
    assert cfg.paged_row_kind()["kind"] == "latent"
    assert not hasattr(cfg, "paged_slot_state_for")
