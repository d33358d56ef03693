"""The decode loop one step ahead (ISSUE 40): step N+1 is on the device's
queue before step N's tokens are fetched.

- the served tokens are ``generate()``'s, greedy, keyed-sampled and
  rng-sampled, for mixed lengths and staggered arrivals;
- a row is delivered only to the request it was dispatched for: an ``eos``
  finish, a cancel and a blown deadline each cost one dropped row and emit
  nothing more, and the slot's next tenant, in the same freed blocks,
  serves its reference tokens;
- a request that ends by the count of its tokens, or at the context limit,
  is left out of the step ahead;
- ``step()`` still emits one token a decode-ready sequence a call;
- what reads or moves a sequence from outside the loop fetches the step in
  flight first;
- the counter that says how often the loop ran ahead;
- with a proposer configured nothing is ever in flight;
- the MiMo tiny model's counters, live KV bytes and routed sets are the
  sums of the same programs called serially on the same schedule.
"""

import urllib.request

import numpy as np
import pytest

from tests.unit.test_serving import _SERVING, _tiny_serving

pytestmark = pytest.mark.heavy

_SAMP = {**_SERVING, "sampling": {"enabled": True}}


def _reference(engine, prompt, n, **kw):
    import jax.numpy as jnp

    out = engine.generate(jnp.asarray([list(prompt)]), max_new_tokens=n,
                          **({"do_sample": False} if not kw else kw))
    return [int(t) for t in out[0, len(prompt):]]


def _serving(serving=_SERVING, telemetry=None, **kw):
    from deepspeed_tpu.serving import ServingEngine

    _, engine = _tiny_serving(serving=serving, telemetry=telemetry)
    return engine, ServingEngine(engine, **kw)


def _prompts(lengths, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, 256, n).tolist() for n in lengths]


def _ahead(srv):
    return srv.stats()["decode_ahead"]


# a keyed-sampled stream: the tiny model's greedy one repeats a token, and
# an ``eos`` test needs a token that the stream has not shown before
_HOT = dict(do_sample=True, seed=21, temperature=1.5)


def _first_new(ref, start=2):
    """The first position from ``start`` whose token is new to the stream:
    a request with that token for ``eos`` ends exactly there."""
    return next(i for i in range(start, len(ref)) if ref[i] not in ref[:i])


def _compiles():
    from deepspeed_tpu.telemetry import compile_watch

    compile_watch.install()
    return compile_watch.snapshot()["backend_compiles"]


class FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# ---------------------------------------------------------------------------
# the same tokens
# ---------------------------------------------------------------------------
def _staggered(srv, prompts, news, knobs):
    """Two requests up front, the rest spliced in between decode steps,
    more requests than slots: steps in flight while slots change hands."""
    reqs = [srv.submit(p, max_new_tokens=n, **k)
            for p, n, k in list(zip(prompts, news, knobs))[:2]]
    srv.step()
    srv.step()
    for p, n, k in list(zip(prompts, news, knobs))[2:]:
        reqs.append(srv.submit(p, max_new_tokens=n, **k))
        srv.step()
    srv.drain()
    return reqs


@pytest.mark.parametrize("sampler", ["greedy", "keyed"])
def test_served_tokens_are_generates(sampler):
    from deepspeed_tpu.serving import FINISHED

    engine, srv = _serving(_SAMP if sampler == "keyed" else _SERVING)
    prompts = _prompts((5, 11, 3, 8, 16, 2))
    news = [6, 4, 5, 2, 7, 9]
    knobs = [{} for _ in prompts]
    if sampler == "keyed":
        knobs = [dict(do_sample=True, seed=101, temperature=0.8, top_p=0.9),
                 {}, dict(do_sample=True, seed=303, temperature=1.3, top_k=7),
                 dict(do_sample=True, seed=404), {},
                 dict(do_sample=True, seed=9, temperature=0.7)]
    reqs = _staggered(srv, prompts, news, knobs)
    for req, p, n, k in zip(reqs, prompts, news, knobs):
        assert req.state == FINISHED, (req.state, req.finish_reason)
        assert req.tokens == _reference(engine, p, n, **k), req.request_id
    # every step but the first of a busy stretch was dispatched behind
    # another, and no row was wasted: every ending was known by the count
    ahead, steps = _ahead(srv), srv.stats()["decode_steps"]
    assert 0 < ahead["steps"] < steps and ahead["dropped_rows"] == 0
    assert srv._flight is None
    srv.destroy()


def test_rng_sampled_tokens_are_generates():
    """The engine's own rng stream draws one key a dispatched step, in
    order: with one slot (a draw depends on the batch's shape) each
    request's stream is ``generate()``'s from the key the engine held
    when it was admitted. No step is dispatched that is not fetched, so
    no key is spent on one."""
    serving = {**_SERVING, "decode_slots": 1, "do_sample": True,
               "temperature": 0.9, "top_k": 12, "seed": 7}
    engine, srv = _serving(serving)
    for prompt, n in zip(_prompts((4, 13, 7)), (6, 3, 8)):
        key = srv._rng
        req = srv.submit(prompt, max_new_tokens=n)
        srv.drain()
        assert req.tokens == _reference(
            engine, prompt, n, do_sample=True, temperature=0.9, top_k=12,
            rng=key)
        assert len(set(req.tokens)) > 1
    assert _ahead(srv)["dropped_rows"] == 0
    srv.destroy()


# ---------------------------------------------------------------------------
# a row goes only to the request it was dispatched for
# ---------------------------------------------------------------------------
def test_eos_drops_one_row_and_the_next_tenant_is_untouched():
    """A ends by ``eos`` at step N with step N+1 already dispatched for
    it: nothing is emitted after the ``eos``, the row is counted dropped,
    and C, admitted into A's slot and A's freed blocks (the stale row
    wrote one KV row into one of them) serves its reference tokens, as
    does B beside them."""
    # 2 slots; room for A and B and not one block more: C has to take
    # the blocks A frees
    serving = {**_SAMP, "decode_slots": 2, "num_blocks": 1 + 3 + 3}
    engine, srv = _serving(serving)
    pa, pb, pc = _prompts((9, 6, 11), seed=3)
    ref_a = _reference(engine, pa, 10, **_HOT)
    stop = _first_new(ref_a)
    seen = []
    a = srv.submit(pa, max_new_tokens=12, eos_token_id=ref_a[stop],
                   stream=lambda r, t, d: seen.append((t, d)), **_HOT)
    b = srv.submit(pb, max_new_tokens=14)
    srv.step()
    blocks_a, slot_a = set(srv.block_mgr.owned(a.request_id)), a.slot
    c = srv.submit(pc, max_new_tokens=9)
    while not a.done:
        assert not c.tokens          # queued: no slot, no blocks
        srv.step()
    assert a.finish_reason == "eos" and a.tokens == ref_a[:stop + 1]
    assert seen == [(t, i == stop) for i, t in enumerate(a.tokens)]
    # the step ahead had A's row: in flight, not fetched yet
    assert (slot_a, a) in srv._flight.pairs
    assert _ahead(srv)["dropped_rows"] == 0
    srv.step()                       # admits C; fetches the stale row
    assert c.slot == slot_a
    assert set(srv.block_mgr.owned(c.request_id)) <= blocks_a
    assert _ahead(srv)["dropped_rows"] == 1
    srv.drain()
    assert len(seen) == stop + 1 and a.length == len(pa) + stop
    assert b.tokens == _reference(engine, pb, 14)
    assert c.tokens == _reference(engine, pc, 9)
    assert _ahead(srv)["dropped_rows"] == 1
    srv.destroy()


def test_the_last_sequence_ending_by_eos_leaves_nothing_in_flight():
    engine, srv = _serving(_SAMP)
    prompt, = _prompts((7,), seed=4)
    ref = _reference(engine, prompt, 8, **_HOT)
    stop = _first_new(ref)
    req = srv.submit(prompt, max_new_tokens=8, eos_token_id=ref[stop],
                     **_HOT)
    done = []
    while srv.pending:
        done += srv.step()
        assert req.done or srv._flight is not None
    assert done == [req] and req.tokens == ref[:stop + 1]
    # the step ahead, which no sequence needed any more, was fetched and
    # its one row dropped before step() returned
    assert srv._flight is None and _ahead(srv)["dropped_rows"] == 1
    assert srv.stats()["decode_steps"] == stop + 1
    srv.destroy()


@pytest.mark.parametrize("how", ["cancel", "deadline"])
def test_a_request_leaves_while_its_row_is_in_flight(how):
    clock = FakeClock()
    engine, srv = _serving(clock=clock)
    pa, pb = _prompts((6, 10), seed=5)
    seen = []
    a = srv.submit(pa, max_new_tokens=12, deadline_ms=500.0,
                   stream=lambda r, t, d: seen.append(t))
    b = srv.submit(pb, max_new_tokens=9)
    srv.step()
    srv.step()
    assert (a.slot, a) in srv._flight.pairs and len(a.tokens) == 3
    if how == "cancel":
        assert srv.cancel(a.request_id)
    else:
        clock.t = 1.0                # the sweep of the next step() sheds it
    srv.step()
    assert a.done and a.finish_reason == (
        "cancelled" if how == "cancel" else "deadline")
    assert len(a.tokens) == 3 and seen == a.tokens
    assert a.tokens == _reference(engine, pa, 3)
    assert _ahead(srv)["dropped_rows"] == 1
    srv.drain()
    assert b.tokens == _reference(engine, pb, 9)
    assert _ahead(srv)["dropped_rows"] == 1 and seen == a.tokens
    srv.destroy()


@pytest.mark.parametrize("ends_by", ["max_tokens", "window"])
def test_an_ending_known_by_the_count_is_left_out_of_the_step_ahead(ends_by):
    """Neither ending needs the fetch to be known: every dispatched row is
    delivered, and the last call of the stretch dispatches nothing."""
    engine, srv = _serving()
    prompt, = _prompts((9,), seed=6)
    n = 6
    req = srv.submit(prompt, max_new_tokens=n if ends_by == "max_tokens"
                     else n + 5)
    if ends_by == "window":
        # (admission refuses what cannot fit the context, so the limit is
        # reached only by a sequence it did not size: make it one)
        srv.max_len = len(prompt) + n - 1
    calls = 0
    while srv.pending:
        srv.step()
        calls += 1
        # one token a call, and the first call's prefill token besides
        assert len(req.tokens) == calls + 1
        assert (srv._flight is None) == req.done
    assert req.finish_reason == ends_by
    assert req.tokens == _reference(engine, prompt, n)
    stats = srv.stats()
    assert stats["decode_steps"] == n - 1 == calls
    assert stats["decode_ahead"] == {"steps": n - 2, "dropped_rows": 0}
    srv.destroy()


def test_step_emits_one_token_a_decode_ready_sequence_every_call():
    """The first call included (it dispatches two steps and fetches one);
    a sequence that goes live in a call is decode-ready from the next: the
    step fetched in its first call was dispatched before it was there."""
    _, srv = _serving()
    a, b = (srv.submit(p, max_new_tokens=9) for p in _prompts((5, 12)))
    srv.step()
    assert [len(a.tokens), len(b.tokens)] == [2, 2]
    assert srv.stats()["decode_steps"] == 1 and srv._flight is not None
    c = srv.submit(_prompts((4,), seed=8)[0], max_new_tokens=9)
    srv.step()
    assert [len(r.tokens) for r in (a, b, c)] == [3, 3, 1]
    assert [r for _, r in srv._flight.pairs] == [a, b, c]
    for k in range(3):
        srv.step()
        assert [len(r.tokens) for r in (a, b, c)] == [4 + k, 4 + k, 2 + k]
        assert srv.stats()["decode_steps"] == 3 + k
    srv.destroy()


# ---------------------------------------------------------------------------
# flush points
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("call", ["export_sequence", "migrate_out",
                                  "import_sequence", "destroy", "drain"])
def test_a_reader_from_outside_the_loop_fetches_the_step_in_flight(call):
    engine, srv = _serving()
    pa, pb = _prompts((6, 9), seed=9)
    a = srv.submit(pa, max_new_tokens=10)
    b = srv.submit(pb, max_new_tokens=4)   # ends at the step in flight
    srv.step()
    srv.step()
    assert srv._flight is not None
    assert [len(a.tokens), len(b.tokens)] == [3, 3]
    if call == "export_sequence":
        export = srv.export_sequence(a.request_id)
        assert export["tokens"] == a.tokens and export["length"] == a.length
        assert export["last_token"] == a.tokens[-1]
    elif call == "migrate_out":
        assert srv.migrate_out(a.request_id)
    elif call == "import_sequence":
        _, other = _serving()
        other.engine.params = engine.params
        c = other.submit(_prompts((5,), seed=10)[0], max_new_tokens=8)
        other.step()
        moved = srv.import_sequence(other.export_sequence(c.request_id))
        assert other._flight is None and moved is not None
        assert moved.tokens == c.tokens and len(c.tokens) == 3
        other.destroy()
    elif call == "drain":
        assert srv.drain(max_steps=0) == [b]
    else:
        srv.destroy()
    assert srv._flight is None
    assert [len(a.tokens), len(b.tokens)] == [4, 4]
    assert b.done and b.tokens == _reference(engine, pb, 4)
    assert a.tokens == _reference(engine, pa, 4)
    if call not in ("destroy", "drain"):
        # what the flush finished is reported by the next step()
        assert b in srv.step()
        srv.destroy()


# ---------------------------------------------------------------------------
# the counter, and the path it does not count
# ---------------------------------------------------------------------------
def test_the_counters_say_how_often_the_loop_ran_ahead(tmp_path):
    engine, srv = _serving(_SAMP, telemetry={
        "enabled": True, "dir": str(tmp_path), "jsonl": False,
        "memory": False, "metrics_port": 0})
    prompt, other = _prompts((7, 5), seed=11)
    ref = _reference(engine, prompt, 9, **_HOT)
    stop = _first_new(ref)
    srv.submit(prompt, max_new_tokens=9, eos_token_id=ref[stop], **_HOT)
    srv.drain()
    srv.submit(other, max_new_tokens=5)
    srv.drain()
    stats = srv.stats()
    # the eos stretch: stop + 1 steps, all but the first behind another;
    # the other: 4 steps, the last call dispatched none
    assert stats["decode_ahead"] == {"steps": stop + 3, "dropped_rows": 1}
    assert stats["decode_steps"] == stop + 1 + 4
    body = urllib.request.urlopen(
        srv.telemetry._metrics_server.url, timeout=10).read().decode()
    for needle in (f"ds_serving_decode_ahead_steps_total {stop + 3}",
                   "ds_serving_decode_dropped_rows_total 1",
                   f"ds_serving_busy_slot_steps_total {stop + 1 + 4}"):
        assert needle in body, f"scrape missing {needle}"
    srv.reset_stats()
    assert srv.stats()["decode_ahead"] == {"steps": 0, "dropped_rows": 0}
    srv.destroy()


def test_the_steps_are_counted_by_who_wrote_their_kv_rows(monkeypatch):
    """``stats()["kv_write"]`` beside ``decode_steps``: a step whose paged
    kernel call wrote the step's rows itself (``"kernel"``: GPT-2's decode
    program on the Pallas kernel, asked of the model here, since the loop
    one step ahead cannot run under the interpreter) or whose program
    scattered them (``"scatter"``: the dense gather path of a machine with
    no TPU, every ``k + 1``-row verify step, and a step of more busy rows
    than ``paged_most_writers``)."""
    from deepspeed_tpu.ops import attention as attn_mod

    _, srv = _serving()
    for p, n in zip(_prompts((7, 5), seed=13), (4, 3)):
        srv.submit(p, max_new_tokens=n)
    srv.drain()
    stats = srv.stats()
    assert stats["decode_steps"] == 3
    assert stats["kv_write"] == {"kernel": 0, "scatter": 3}
    srv.reset_stats()
    assert srv.stats()["kv_write"] == {"kernel": 0, "scatter": 3}
    srv.destroy()

    monkeypatch.setattr(attn_mod, "_FORCE_DECODE_KERNEL", True)
    _, srv = _serving()
    # three slots, two pools: two busy rows are written by the call, three
    # are scattered; a verify step's rows always
    assert [srv._kv_write_form(1, busy) for busy in (1, 2, 3)] == [
        "kernel", "kernel", "scatter"]
    assert srv._kv_write_form(3, 1) == "scatter"
    srv._step_boundary(2, np.asarray([9, 0, 12]), 1)
    srv._step_boundary(3, np.asarray([9, 4, 12]), 1)
    assert srv.stats()["kv_write"] == {"kernel": 1, "scatter": 1}
    assert srv.stats()["decode_steps"] == 2
    srv.destroy()
    _, srv = _serving({**_SERVING, "kv_cache_dtype": "int8"})
    assert [srv._kv_write_form(1, busy) for busy in (2, 3)] == ["kernel"] * 2
    srv.destroy()


def test_with_a_proposer_nothing_is_ever_in_flight():
    engine, srv = _serving({**_SERVING, "speculative": {
        "enabled": True, "proposer": "prompt_lookup",
        "num_speculative_tokens": 2}})
    prompts = _prompts((8, 5), seed=12)
    reqs = [srv.submit(p, max_new_tokens=7) for p in prompts]
    while srv.pending:
        srv.step()
        assert srv._flight is None
    for req, p in zip(reqs, prompts):
        assert req.tokens == _reference(engine, p, 7)
    assert srv.stats()["decode_ahead"] == {"steps": 0, "dropped_rows": 0}
    # a verify step's k + 1 rows may straddle two blocks: they scatter
    assert srv.stats()["kv_write"] == {
        "kernel": 0, "scatter": srv.stats()["decode_steps"]}
    assert srv._feed_fn is None and srv._decode_fn is None
    srv.destroy()


@pytest.mark.parametrize("telemetry", [
    None, {"enabled": True, "jsonl": False, "memory": False}],
    ids=["plain", "watched"])
def test_one_short_request_compiles_everything_the_loop_runs(telemetry):
    """The benchmark's warm-up: a request of two tokens is one decode
    step, dispatched with nothing in flight and nothing ahead of it. It
    has to leave no program for the measured window to compile (the
    benchmark counts backend compiles the same way): the feed and the
    decode program it compiled serve every later step, whatever made their
    token input."""
    _, srv = _serving(telemetry=telemetry)
    srv.submit(_prompts((5,))[0], max_new_tokens=2)
    srv.drain()
    assert srv.stats()["decode_steps"] == 1
    assert _ahead(srv) == {"steps": 0, "dropped_rows": 0}
    before = _compiles()
    for p in _prompts((5, 7, 3), seed=13):
        srv.submit(p, max_new_tokens=6)
    srv.drain()
    assert _ahead(srv)["steps"] > 0
    assert _compiles() == before
    srv.destroy()


# ---------------------------------------------------------------------------
# the model's counters are added at the fetch, from the fetched step's view
# ---------------------------------------------------------------------------
def _recording(fn, log, kind):
    def call(*args):
        out = fn(*args)
        log.append((kind, [np.asarray(a) for a in args[2:]]))
        return out
    return call


def test_mimo_counters_are_the_serial_sums_on_the_same_schedule():
    """Record what the engine dispatched, in order; then call a twin
    engine's programs with the same tables and lengths one at a time,
    fetching each before the next and feeding each decode step the tokens
    the call before it returned. The token inputs the engine made on the
    device, its counters, its live KV bytes and its routed sets have to be
    what that serial loop gives."""
    import jax
    import jax.numpy as jnp

    from tests.unit.test_mimo_v2 import FAMILY

    with jax.default_matmul_precision("highest"):
        cfg, _, params = FAMILY.make(ep_size=4, ep_rank=1)
        srv = FAMILY.serving_engine(params, cfg, routed_experts_kept=8)
        twin = FAMILY.serving_engine(params, cfg, routed_experts_kept=8)
        log = []
        build_prefill = srv._build_prefill
        srv._build_prefill = lambda T: _recording(build_prefill(T), log, T)
        srv._decode_fn = _recording(srv._build_decode(), log, "decode")
        prompts = _prompts((5, 19, 9, 12), seed=14)
        prompts = [[t % cfg.vocab_size for t in p] for p in prompts]
        news = [11, 6, 14, 5]
        reqs = [srv.submit(p, max_new_tokens=n)
                for p, n in list(zip(prompts, news))[:2]]
        srv.step()
        srv.step()
        reqs += [srv.submit(p, max_new_tokens=n)
                 for p, n in list(zip(prompts, news))[2:]]
        srv.drain()
        stats = srv.stats()
        assert stats["decode_ahead"]["steps"] > 0
        assert stats["decode_ahead"]["dropped_rows"] == 0
        # the zeros before the first step had the decode output's shape
        # (tokens, counters, routed sets): the feed was traced once
        assert getattr(srv._feed_fn, "_fn", srv._feed_fn)._cache_size() == 1

        # the serial loop, from the twin's own programs
        names = srv._counter_names
        n, width = srv.config.decode_slots, srv._routed_width
        sums = {ph: dict.fromkeys(names, 0) for ph in ("prefill", "decode")}
        kv = {"global": 0, "window": 0}
        held = srv.slot_entries * srv.config.block_size
        last = np.zeros((n,), np.int32)      # the host's tokens, by slot
        routed = {}                          # slot -> rows of its tenant
        finished = []
        decode = twin._build_decode()
        for kind, args in log:
            if kind == "decode":
                tokens, tables, lengths = args[:3]
                live = lengths > 0
                # what the engine fed the program from the device
                assert (tokens[:, 0] == np.where(live, last, 0)).all()
                out, twin.cache = decode(
                    twin.engine.params, twin.cache, jnp.asarray(tokens),
                    jnp.asarray(tables), jnp.asarray(lengths),
                    jax.random.PRNGKey(0))
                out = np.asarray(out)
                last = np.where(live, out[:n], last)
                rows = out[n + len(names):].reshape(n, 1, width)
                for slot in np.flatnonzero(live):
                    routed[slot].append(rows[slot])
                kv["global"] += int(lengths[live].sum()) * (
                    srv._kv_bytes["global"])
                kv["window"] += int(np.minimum(lengths[live], held).sum()) * (
                    srv._kv_bytes["window"])
                phase, counted = "decode", out[n:n + len(names)]
            else:
                ids, table, num_valid = args[:3]
                if kind not in twin._prefill_fns:
                    twin._prefill_fns[kind] = twin._build_prefill(kind)
                out, twin.cache = twin._prefill_fns[kind](
                    twin.engine.params, twin.cache, jnp.asarray(ids),
                    jnp.asarray(table), jnp.asarray(num_valid),
                    jax.random.PRNGKey(0))
                out = np.asarray(out)
                # the slot is the one whose ring the table ends in
                slot = (int(table[0, -1]) - 1) // srv.slot_entries
                if slot in routed:
                    finished.append(np.concatenate(routed[slot]))
                last[slot] = out[0]
                routed[slot] = [out[1 + len(names):].reshape(
                    1, -1, width)[0, :int(num_valid[0])]]
                phase, counted = "prefill", out[1:1 + len(names)]
            for name, c in zip(names, counted):
                sums[phase][name] += int(c)
        assert stats["model_counters"] == sums
        assert stats["kv_live_bytes"] == kv
        assert 0 < sums["decode"]["pairs_here"] < sums["decode"]["pairs_all"]
        finished += [np.concatenate(rows) for rows in routed.values()]
        for req, prompt in zip(reqs, prompts):
            got = srv.routed_experts(req.request_id)
            assert got.shape == (len(prompt) + len(req.tokens) - 1, width)
            assert any(f.shape == got.shape and (f == got).all()
                       for f in finished), req.request_id
        srv.destroy()
        twin.destroy()
