"""``tools/traffic_order_model.py``: the queue model of a serving step loop
that ranked the orders of ``perfbench/traffic/agent-gen.json`` (PERF.md,
PR 49). No device, no jax."""

import json
import os

import pytest

from tools import traffic_order_model as model

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MIX = os.path.join(REPO, "perfbench", "traffic", "agent-gen.json")
LOOP = dict(chunk_tokens=4, chunk_s=1.0, step_s=0.5, row_s=0.0, slots=2)


def _request(due, prompt, new):
    return {"due_s": due, "prompt": [0] * prompt, "max_new_tokens": new}


@pytest.mark.parametrize("window, tokens", [
    # one prompt of two chunks (2 s: its first token), then a token a step
    (1.9, 0), (2.1, 1), (2.6, 2), (3.6, 3), (60.0, 3)])
def test_a_request_is_its_chunks_then_a_token_a_step(window, tokens):
    assert model.tokens_in_window([_request(0.0, 8, 3)], window,
                                  **LOOP) == tokens


def test_a_chunk_ahead_delays_every_live_rows_step():
    alone = model.tokens_in_window([_request(0.0, 4, 9)], 4.0, **LOOP)
    behind = model.tokens_in_window(
        [_request(0.0, 4, 9), _request(1.2, 12, 2)], 4.0, **LOOP)
    # its first token at 1 s and five steps of 0.5 s alone; with three more
    # chunks in the loop each step waits a second behind one
    assert (alone, behind) == (6, 3)


def test_a_third_request_waits_for_a_slot():
    reqs = [_request(0.0, 4, 4), _request(0.0, 4, 4), _request(0.0, 4, 2)]
    assert model.tokens_in_window(reqs, 60.0, **LOOP) == 10
    assert model.tokens_in_window(reqs, 60.0, **{**LOOP, "slots": 3}) == 10
    # by 4.1 s: with a slot each the third prompt's chunk delays the steps
    # of the other two; with two slots it waits behind them
    assert model.tokens_in_window(reqs, 4.1, **LOOP) == 7
    assert model.tokens_in_window(reqs, 4.1, **{**LOOP, "slots": 3}) == 6


def test_a_stall_and_a_slower_engine_lose_tokens_at_the_cut():
    reqs = [_request(0.1 * i, 4, 6) for i in range(8)]
    base = model.tokens_in_window(reqs, 8.0, **LOOP)
    assert model.tokens_in_window(reqs, 8.0, speed=0.8, **LOOP) < base
    assert model.tokens_in_window(reqs, 8.0, stall=(2.0, 1.5), **LOOP) < base
    assert model.tokens_in_window(reqs, 8.0, speed=1.25, **LOOP) > base


def test_the_chosen_order_moves_less_with_speed_than_the_first_drawn():
    with open(MIX) as f:
        mix = json.load(f)
    loop = dict(chunk_tokens=512, chunk_s=0.046, step_s=0.018,
                row_s=0.00015, slots=64)
    first = model.score(mix, 4900000049, 50.0, 1.5, **loop)
    chosen = model.score(mix, mix["schedule_seed"], 50.0, 1.5, **loop)
    assert mix["schedule_seed"] == chosen["schedule_seed"] == 4900000285
    # the readings PERF.md gives: 0.94 and 0.39 of a change of speed
    assert first["per_speed"] == pytest.approx(0.94, abs=0.01)
    assert chosen["per_speed"] == pytest.approx(0.39, abs=0.01)
    assert chosen["stall_loss"] < 0.5 * first["stall_loss"]
    # the model is within 3% of the chip's 742-744 tokens a second
    assert chosen["tokens_s"] == pytest.approx(743, rel=0.03)


def test_the_command_line(capsys, tmp_path):
    assert model.main([MIX]) == 0
    row = json.loads(capsys.readouterr().out)
    assert row["schedule_seed"] == 4900000285
    assert model.main([MIX, "--rank", "3", "--from-seed", "7",
                       "--window", "10"]) == 0
    rows = [json.loads(ln) for ln in capsys.readouterr().out.splitlines()]
    assert sorted(r["schedule_seed"] for r in rows) == [7, 8, 9]
    keys = [r["per_speed"] + 10 * r["stall_loss"] for r in rows]
    assert keys == sorted(keys)
    train = os.path.join(REPO, "perfbench", "traffic", "pretrain-1024.json")
    assert model.main([train]) == 2
    assert model.main([]) == 2
