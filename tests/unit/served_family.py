"""What the served families' unit files share (no test lives here): a
family's file names its config, model and reference classes, its
``shape_of``, its tolerance and its serving block's defaults in ONE
``Family``; this kit makes each set of parameters once (``jax.jit`` around
``module.init``), runs the plain call and the reference as ONE compiled
program each (op by op an un-jitted pass compiles some hundreds), keeps ONE
``ServingEngine`` a (config, parameters, serving block) for the tests that
serve requests and read the result, and drives an engine's paged module
with the engine's own pools and tables to keep LOGITS.

What sharing an engine asks of a test: counters are read over the test's own
requests (``served_logits_match`` resets the window first); a request left
in flight is drained; a pool that was poisoned is put back; a test that
patches what an engine's programs trace, or needs a pool no other test has
written, asks ``serving_engine`` for one of its own and destroys it."""

import dataclasses
import types
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import deepspeed_tpu
from deepspeed_tpu.parallel.topology import MeshTopology, reset_topology
from deepspeed_tpu.serving import ServingEngine

# served with ``serving.<mechanism>`` set, every family refuses by name
REFUSED = pytest.mark.parametrize("serving, mechanism", [
    ({"prefix_cache": True}, "serving.prefix_cache"),
    ({"speculative": {"num_speculative_tokens": 2}}, "serving.speculative"),
    ({"kv_cache_dtype": "int8"}, "serving.kv_cache_dtype"),
], ids=["prefix-cache", "speculation", "int8-kv"])


@pytest.fixture
def highest():
    # the CPU multiplies float32 exactly; the setting is the chip's, kept so
    # that the test says what it compares
    with jax.default_matmul_precision("highest"):
        yield


def prompts(cfg, lengths, seed=5):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab_size, n).tolist() for n in lengths]


def _frozen(value):
    if isinstance(value, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in value.items()))
    return value


@dataclasses.dataclass(eq=False)
class Family:
    config: type                  # has ``.tiny(dtype=..., **kw)``
    model: type
    reference: types.ModuleType   # ``perfbench.reference_<family>``
    shape_of: Callable            # config -> the reference's view of it
    tol: float
    serving: dict                 # the serving block's defaults
    perturb: Callable = None      # parameters -> parameters, after init
    # a whole prompt's bucket in ``paged_logits``: the next whole 8, plus this
    # many columns (8: a bucket the prompt never fills)
    bucket_slack: int = 8
    aux_of: Callable = None       # the paged call's aux -> rows kept a step

    def __post_init__(self):
        self._made, self._plain, self._reference, self._engines = {}, {}, {}, {}

    # -- parameters and the two whole-model programs ------------------------
    def make(self, dtype=jnp.float32, seed=0, **kw):
        """``(config, module, parameters)``, made once for its arguments."""
        key = (dtype, seed, _frozen(kw))
        if key not in self._made:
            cfg = self.config.tiny(dtype=dtype, **kw)
            module = self.model(cfg)
            params = jax.jit(module.init)(
                jax.random.PRNGKey(seed),
                jnp.zeros((1, 8), jnp.int32))["params"]
            if self.perturb is not None:
                params = jax.jit(self.perturb)(params)
            self._made[key] = cfg, module, params
        return self._made[key]

    def plain(self, cfg):
        """``(parameters, ids) -> logits``: the model's plain call under
        ``cfg``, one program a config."""
        if cfg not in self._plain:
            module = self.model(cfg)
            self._plain[cfg] = jax.jit(
                lambda p, ids: module.apply({"params": p}, ids))
        return self._plain[cfg]

    def reference_program(self, cfg, call="logits", **kw):
        """``(parameters, ids) -> reference.<call>(...)`` under ``cfg`` with
        ``kw`` handed on, one program a (config, call, ``kw``)."""
        key = cfg, call, _frozen(kw)
        if key not in self._reference:
            shape, fn = self.shape_of(cfg), getattr(self.reference, call)
            self._reference[key] = jax.jit(
                lambda p, ids: fn(p, ids, shape, **kw))
        return self._reference[key]

    def _padded(self, cfg, params, ids, call):
        """``call`` of the reference over ``ids`` padded on the right to a
        whole 64 (causal: unseen), so that one program serves a test file's
        lengths. -> (its output over the padded ids, the real length)."""
        ids = np.asarray(ids)
        rows, t = ids.shape
        wide = np.zeros((rows, -(-t // 64) * 64), np.int32)
        wide[:, :t] = ids
        return np.asarray(self.reference_program(cfg, call)(
            params, jnp.asarray(wide))), t

    def reference_logits(self, cfg, params, ids):
        out, t = self._padded(cfg, params, ids, "logits")
        return out[:, :t]

    # -- engines ------------------------------------------------------------
    def serving_engine(self, params, cfg, one_device=True, **serving):
        """An engine of the caller's own (to destroy), on ONE device as a
        one-chip cell's is: over the tests' eight virtual devices every
        program would be partitioned eight ways and run eight times over,
        for nothing these files assert. (``one_device`` False: the default
        mesh of all eight, where the engine lays out no weight as it starts
        and so compiles nothing before a test's first call.)"""
        reset_topology()
        mesh = MeshTopology(axis_sizes={"data": 1},
                            devices=jax.devices()[:1]) if one_device else None
        return ServingEngine(deepspeed_tpu.init_inference(
            self.model(cfg), params=params, dtype=cfg.dtype, mesh=mesh,
            serving={**self.serving, **serving}))

    def shared_engine(self, params, cfg, **serving):
        """THE engine of (config, parameters, serving block), built at its
        first use and destroyed with the module (``engines``)."""
        key = cfg, id(params), _frozen(serving)
        if key not in self._engines:
            # (the parameters are held so that their id stays theirs)
            self._engines[key] = self.serving_engine(params, cfg,
                                                     **serving), params
        return self._engines[key][0]

    def destroy_engines(self):
        for srv, _ in self._engines.values():
            srv.destroy()
        self._engines.clear()

    def engines(self):
        """A module-scoped autouse fixture for the family's file."""
        @pytest.fixture(scope="module", autouse=True)
        def engines():
            yield
            self.destroy_engines()
        return engines

    # -- serving through an engine -------------------------------------------
    def served_logits_match(self, cfg, params, requests, **serving):
        """Serve ``requests`` [(prompt, new tokens)] greedily through the
        shared engine of ``serving``; every served token has to be the
        reference's argmax at its position, on the reference's logits over
        prompt + served tokens (the tiny model's logits are separated by far
        more than the tolerance; a tie inside it aside). -> (the engine's
        stats over these requests, the requests)."""
        srv = self.shared_engine(params, cfg, **serving)
        srv.reset_stats()
        steps = srv._step_count
        reqs = [srv.submit(p, max_new_tokens=n) for p, n in requests]
        srv.drain()
        stats = srv.stats()
        stats["decode_steps"] -= steps     # the one count no reset clears
        for req, (prompt, n) in zip(reqs, requests):
            assert len(req.tokens) == n, (req.state, req.finish_reason)
            want = self.reference_logits(
                cfg, params, [list(prompt) + req.tokens])[0]
            for k, tok in enumerate(req.tokens):
                row = want[len(prompt) - 1 + k]
                assert row.max() - row[tok] <= self.tol, (k, tok,
                                                          row.argmax())
        return stats, reqs

    def routed_sets_are_the_references(self, srv, cfg, params, req, prompt):
        """``srv.routed_experts`` of a finished request against the
        reference's own sets over the tokens it processed."""
        got = srv.routed_experts(req.request_id)
        want, fed = self._padded(cfg, params,
                                 [list(prompt) + req.tokens[:-1]],
                                 "routed_sets")               # [L, 1, T, k]
        want = want[:, 0, :fed]
        sparse, _, k = want.shape
        assert k == cfg.num_experts_per_tok
        assert got.shape == (fed, sparse * k)
        got = got.reshape(-1, sparse, k).transpose(1, 0, 2)
        assert (np.sort(got, -1) == np.sort(want, -1)).all()

    def _paged_calls(self, srv, retrace):
        """The engine's paged module as two programs (whole-prompt prefill,
        and everything that reads the cache), traced once an engine;
        ``retrace``: anew, for a test that patched what they trace."""
        calls = getattr(srv, "_kit_calls", None)
        if calls is None or retrace:
            dm, pick = srv._dmodule, self.aux_of

            def call(prefill):
                def fn(params, cache, ids, tables, lengths, num_valid):
                    out, v = dm.apply(
                        {"params": params, "cache": cache}, ids,
                        mutable=["cache"],
                        paging={"block_tables": tables, "lengths": lengths,
                                "num_valid": num_valid, "prefill": prefill})
                    return out[0], pick and pick(out[1]), v["cache"]
                return jax.jit(fn)

            calls = call(True), call(False)
            if not retrace:
                srv._kit_calls = calls
        return calls

    def paged_logits(self, srv, prompt, steps, slot=1, chunk=0,
                     one_device=False, spoil=None, retrace=False):
        """Drive the engine's own paged module with its own pools and tables,
        as its programs do, and keep the LOGITS: every prompt position (the
        whole prompt right-padded into its bucket, or chunks of ``chunk``),
        then ``steps`` greedy decode steps in the decode program's batch
        shape, the other slots idle; ``spoil(cache) -> cache`` runs between
        the two. -> (logits [positions, vocab], ids), and with ``aux_of`` the
        rows it kept a position. A model whose paged call hands back ONE row
        a sequence (``PagedDecoder.rows_from``) gives a chunk's last
        position alone: ``self.positions`` says which positions the rows
        are."""
        params = srv.engine.params
        if one_device:
            # the Pallas interpreter's callbacks do not go through the SPMD
            # partitioner an eight-device mesh brings
            params, srv.cache = jax.device_put((params, srv.cache),
                                               jax.devices()[0])
        whole, cached = self._paged_calls(srv, retrace)
        rid = f"direct-{slot}-{len(prompt)}"
        table = srv._slot_table(slot, srv.block_mgr.allocate(
            rid, len(prompt) + steps))
        i32 = lambda x: jnp.asarray(x, jnp.int32)
        rows, kept, n = [], [], len(prompt)
        self.positions = where = []
        try:
            for at in range(0, n, chunk or n):
                m = min(chunk or n, n - at)
                ids = np.zeros((1, chunk or (-(-n // 8) * 8
                                             + self.bucket_slack)), np.int32)
                ids[0, :m] = prompt[at:at + m]
                lg, aux, srv.cache = (cached if chunk else whole)(
                    params, srv.cache, i32(ids), i32(table[None]), i32([at]),
                    i32([m]))
                if lg.shape[1] == ids.shape[1]:
                    rows.append(np.asarray(lg[0, :m]))
                    where.extend(range(at, at + m))
                else:                   # the call's last real row alone
                    rows.append(np.asarray(lg[0]))
                    where.append(at + m - 1)
                if aux is not None:
                    kept.append(np.asarray(aux[0, :m]))
            if spoil is not None:
                srv.cache = spoil(srv.cache)
            slots = srv.config.decode_slots
            tables = np.zeros((slots, len(table)), np.int32)
            tables[slot] = table
            tokens = list(prompt)
            for _ in range(steps):
                tokens.append(int(rows[-1][-1].argmax()))
                lengths = np.zeros(slots, np.int32)
                last = np.zeros((slots, 1), np.int32)
                lengths[slot], last[slot] = len(tokens) - 1, tokens[-1]
                lg, aux, srv.cache = cached(
                    params, srv.cache, i32(last), i32(tables), i32(lengths),
                    jnp.ones(slots, jnp.int32))
                rows.append(np.asarray(lg[slot]))
                where.append(len(tokens) - 1)
                if aux is not None:
                    kept.append(np.asarray(aux[slot]))
        finally:
            srv.block_mgr.release(rid)
        if self.aux_of is None:
            return np.concatenate(rows), tokens
        return np.concatenate(rows), tokens, np.concatenate(kept)

    def paged_logits_match(self, srv, cfg, params, prompt, steps, **kw):
        """``paged_logits`` against the reference's ONE full forward pass
        over the same tokens: the largest difference on logits."""
        got, tokens = self.paged_logits(srv, prompt, steps, **kw)[:2]
        want = self.reference_logits(cfg, params, [tokens])[0]
        return np.abs(got - want[self.positions]).max()

    def decode_through_the_kernels(self, monkeypatch, cfg, params, prompt,
                                   steps, chunk=0, experts=True):
        """The same prefill and decode steps twice: on the XLA paths (the
        shared engine), and with the Pallas decode kernels (and, with
        ``experts``, the grouped expert matmul) in the programs, in interpret
        mode, through an engine built under the patches. -> (the kernels'
        logits, the XLA paths', the paths counted)."""
        from deepspeed_tpu.moe import dropless
        from deepspeed_tpu.ops import attention as ops_attention
        from deepspeed_tpu.utils.compat import tpu_interpret_mode

        want, _ = self.paged_logits(self.shared_engine(params, cfg), prompt,
                                    steps, chunk=chunk)[:2]
        monkeypatch.setattr(ops_attention, "use_decode_kernel", lambda: True)
        if experts:
            ffn = dropless.expert_ffn
            monkeypatch.setattr(dropless, "expert_ffn", lambda *a, **k: ffn(
                *a, **{**k, "use_kernel": True}))
        # on the default mesh: a one-device engine compiles its decode
        # program as it starts, outside interpret mode
        srv = self.serving_engine(params, cfg, one_device=False)
        try:
            with tpu_interpret_mode():
                got, _ = self.paged_logits(srv, prompt, steps, chunk=chunk,
                                           one_device=True)[:2]
            return got, want, srv.stats()["attention_paths"]
        finally:
            srv.destroy()

    # -- refusals -------------------------------------------------------------
    def mechanism_refusal(self, serving, mechanism) -> str:
        cfg, _, params = self.make()
        with pytest.raises(Exception,
                           match=mechanism.replace(".", r"\.")) as e:
            self.serving_engine(params, cfg, **serving)
        assert self.model.__name__ in str(e.value)
        return str(e.value)

    def tensor_parallel_refusal(self) -> str:
        cfg, _, params = self.make()
        reset_topology()
        with pytest.raises(Exception, match="tp_size > 1") as e:
            ServingEngine(deepspeed_tpu.init_inference(
                self.model(cfg), params=params, dtype=cfg.dtype,
                tensor_parallel={"tp_size": 2},
                serving={"decode_slots": 2,
                         "block_size": self.serving["block_size"],
                         "max_model_len": 32}))
        reset_topology()
        return str(e.value)

    def migration_refusals(self) -> list:
        """Export and import of a sequence in flight on the shared engine,
        both refused: the two messages. The sequence is served to its end."""
        cfg, _, params = self.make()
        srv = self.shared_engine(params, cfg)
        req = srv.submit([1, 2, 3, 4, 5], max_new_tokens=8)
        srv.step()
        said = []
        for call in (lambda: srv.export_sequence(req.request_id),
                     lambda: srv.import_sequence({"request_id": "x"})):
            with pytest.raises(NotImplementedError, match="migration") as e:
                call()
            said.append(str(e.value))
        srv.drain()
        return said
