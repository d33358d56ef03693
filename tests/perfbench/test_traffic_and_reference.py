"""The traffic generator is reproducible and has the distribution its
parameters say; the plain reference agrees with ``models/gpt2.py``."""

import json
import math
import os

import numpy as np
import pytest

from perfbench import flops, reference_gpt2, traffic
from perfbench.families import gpt2 as gpt2_family

from .conftest import REPO

CHAT = os.path.join(REPO, "perfbench", "traffic", "chat.json")


def _chat(fixed_schedule=False):
    """The chat mix; without its ``schedule_seed`` unless asked, so that
    the tests see what ``--seed`` does to the order."""
    with open(CHAT) as f:
        mix = json.load(f)
    if not fixed_schedule:
        mix.pop("schedule_seed")
    return mix


def test_a_schedule_seed_fixes_sizes_and_arrivals_for_every_seed():
    a = traffic.requests(_chat(True), 1, 50.0, 50257)
    b = traffic.requests(_chat(True), 2, 50.0, 50257)
    assert [(r["due_s"], len(r["prompt"]), r["max_new_tokens"]) for r in a] \
        == [(r["due_s"], len(r["prompt"]), r["max_new_tokens"]) for r in b]
    assert [r["prompt"] for r in a] != [r["prompt"] for r in b]
    assert len(a) == 55


def test_requests_are_reproducible_from_the_seed():
    a = traffic.requests(_chat(), 3_000_000_001, 20.0, 50257)
    b = traffic.requests(_chat(), 3_000_000_001, 20.0, 50257)
    c = traffic.requests(_chat(), 7, 20.0, 50257)
    assert a == b
    assert [r["prompt"] for r in a] != [r["prompt"] for r in c]


def test_every_seed_gets_the_same_work_in_another_order():
    a = traffic.requests(_chat(), 1, 20.0, 50257)
    b = traffic.requests(_chat(), 2, 20.0, 50257)
    assert len(a) == len(b)
    assert sorted(len(r["prompt"]) for r in a) == \
        sorted(len(r["prompt"]) for r in b)
    assert [len(r["prompt"]) for r in a] != [len(r["prompt"]) for r in b]
    # the gap after the last request runs to the end of the window
    gaps = [np.sort(np.diff([r["due_s"] for r in x] + [20.0]))
            for x in (a, b)]
    assert np.allclose(gaps[0], gaps[1], atol=1e-9)


def test_rate_and_length_quantiles_match_the_parameters():
    mix = _chat()
    seconds = 60.0
    reqs = traffic.requests(mix, 5, seconds, 50257)
    rate = mix["arrivals"]["rate_per_s"]
    assert len(reqs) == round(rate * seconds)
    due = np.array([r["due_s"] for r in reqs])
    assert (np.diff(due) >= 0).all() and 0 <= due[0] and due[-1] < seconds
    gaps = np.diff(due)
    # exponential gaps: mean 1/rate, standard deviation about the mean
    assert gaps.mean() == pytest.approx(1 / rate, rel=0.02)
    assert gaps.std() == pytest.approx(1 / rate, rel=0.1)
    for key, field in (("prompt_len", "prompt"), ("new_tokens", None)):
        spec = mix[key]
        vals = np.array([len(r["prompt"]) if field else r["max_new_tokens"]
                         for r in reqs])
        assert vals.min() >= spec["min"] and vals.max() <= spec["max"]
        assert np.median(vals) == pytest.approx(spec["median"], rel=0.03)
        # the 84th percentile of a lognormal is median * exp(sigma)
        want = min(spec["median"] * math.exp(spec["sigma"]), spec["max"])
        assert np.percentile(vals, 84.1) == pytest.approx(want, rel=0.05)
    assert all(len(r["prompt"]) + r["max_new_tokens"] <= mix["max_total"]
               for r in reqs)
    assert all(0 <= t < 50257 for r in reqs[:5] for t in r["prompt"])


def test_bursts_and_all_at_zero():
    mix = _chat()
    mix["arrivals"] = {"process": "poisson", "rate_per_s": 8.0,
                       "bursts": {"every_s": 10, "for_s": 3, "times": 3}}
    due = np.array([r["due_s"] for r in
                    traffic.requests(mix, 1, 30.0, 1000)])
    rate = mix["arrivals"]["rate_per_s"]
    assert len(due) == round(rate * (3 * 3 * 3 + 21))
    in_burst = ((due % 10) < 3).sum()
    assert in_burst == pytest.approx(rate * 27, rel=0.05)
    mix = _chat()
    mix["arrivals"] = {"process": "all_at_zero", "count": 16}
    reqs = traffic.requests(mix, 1, 30.0, 1000)
    assert len(reqs) == 16 and all(r["due_s"] == 0 for r in reqs)
    assert len({tuple(r["prompt"]) for r in reqs}) == 16


def test_train_batches_are_fresh_each_step_and_seeded():
    mix = {"kind": "train_batches", "seq_len": 16}
    a = traffic.train_batch(mix, 2**31 + 9, 0, 4, 100)
    assert a.shape == (4, 16) and a.dtype == np.int32
    assert (a == traffic.train_batch(mix, 2**31 + 9, 0, 4, 100)).all()
    assert (a != traffic.train_batch(mix, 2**31 + 9, 1, 4, 100)).any()
    with pytest.raises(ValueError):
        traffic.requests(mix, 0, 1.0, 100)


@pytest.mark.parametrize("config,parameters,layers,width", [
    ("gpt2-medium", 354_823_168, 24, 1024),
    ("gpt2-xl", 1_557_611_200, 48, 1600),
])
def test_the_family_counts_what_the_files_say(config, parameters, layers,
                                              width):
    """N and 6N + 12LTd, which ``mfu.train`` stands on, to the digit."""
    with open(os.path.join(REPO, "perfbench", "configs",
                           f"{config}.json")) as f:
        config_file = json.load(f)
    assert gpt2_family.param_count(config_file) == parameters == \
        config_file["parameters"]
    assert gpt2_family.train_flops_per_token(config_file, 1024) == \
        6 * parameters + 12 * layers * 1024 * width
    assert gpt2_family.attention_shapes(config_file) == {
        "heads": config_file["model"]["n_head"],
        "kv_heads": config_file["model"]["n_head"], "head_dim": 64,
        "paged_layers": layers}
    assert gpt2_family.vocab_size(config_file) == 50257
    assert gpt2_family.max_context(config_file) == 1024


def test_flops_and_peaks():
    with open(os.path.join(REPO, "perfbench", "configs", "gpt2-xl.json")) as f:
        xl = json.load(f)
    assert gpt2_family.param_count(xl) == xl["parameters"] == 1_557_611_200
    assert gpt2_family.train_flops_per_token(xl, 1024) == \
        6 * 1_557_611_200 + 12 * 48 * 1024 * 1600
    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError, match="TPU v9"):
        flops.peaks("TPU v9")
    # 8 x 16 heads x 1024 x 64: forward 4*B*H*T*T*D/2, backward twice that
    assert flops.flash_train_flops(8, 16, 1024, 64) == \
        3 * 4 * 8 * 16 * 1024 * 1024 * 64 / 2


def test_reference_agrees_with_the_program_in_float32():
    import jax
    import jax.numpy as jnp

    from deepspeed_tpu.models.gpt2 import (GPT2Config, GPT2LMHeadModel,
                                           gpt2_loss_fn)

    cfg = GPT2Config(vocab_size=211, n_positions=48, n_embd=32, n_layer=3,
                     n_head=4, dtype=jnp.float32, scan_layers=True)
    model = GPT2LMHeadModel(cfg)
    ids = jax.random.randint(jax.random.PRNGKey(1), (2, 40), 0, 211)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    # random biases and LN parameters too, or half the equations go untested
    leaves, tree = jax.tree_util.tree_flatten(params)
    keys = jax.random.split(jax.random.PRNGKey(2), len(leaves))
    params = jax.tree_util.tree_unflatten(tree, [
        x + 0.05 * jax.random.normal(k, x.shape) for x, k in zip(leaves, keys)])
    with jax.default_matmul_precision("highest"):
        want = model.apply({"params": params}, ids)
        want_loss = gpt2_loss_fn(model)(params, {"input_ids": ids})
    got = reference_gpt2.logits(params, ids, cfg.n_head)
    # float32 both sides, sums in another order: a few 1e-6 of |logit| ~ 1
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)
    nll, n = reference_gpt2.next_token_loss(params, ids, cfg.n_head)
    assert n == 2 * 39
    assert float(nll) / n == pytest.approx(float(want_loss), abs=1e-5)
