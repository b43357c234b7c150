"""The traffic generators: the same seed gives the same inputs, another
seed other inputs, and every seed the same amount of work."""
import json
import os

import jax
import numpy as np
import pytest

from bench import common, gen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
with open(os.path.join(ROOT, "bench", "traffic", "serve-poisson.json")) as f:
    SERVE = json.load(f)

BIG = 2 ** 33 + 12345  # wider than 32 bits


@pytest.mark.parametrize("seed", [0, 7, BIG])
def test_open_loop_repeats_for_a_seed(seed):
    (d1, p1, o1), r1 = gen.open_loop(SERVE, seed, 30)
    (d2, p2, o2), r2 = gen.open_loop(SERVE, seed, 30)
    assert np.array_equal(d1, d2) and np.array_equal(p1, p2)
    assert np.array_equal(o1, o2)
    assert np.array_equal(r1.integers(0, 100, 8), r2.integers(0, 100, 8))


def test_open_loop_differs_across_seeds_with_the_same_work():
    (d1, p1, o1), r1 = gen.open_loop(SERVE, 1, 30)
    (d2, p2, o2), r2 = gen.open_loop(SERVE, BIG, 30)
    # the same schedule, other prompts
    assert np.array_equal(d1, d2) and np.array_equal(p1, p2)
    assert np.array_equal(o1, o2)
    assert not np.array_equal(r1.integers(0, 50304, 64),
                              r2.integers(0, 50304, 64))
    # the file's order_seed draws the schedule: sizes and gaps in another
    # order, the same multisets
    other = dict(SERVE, order_seed=SERVE["order_seed"] + 1)
    (d3, p3, o3), _ = gen.open_loop(other, 1, 30)
    assert not np.array_equal(d1, d3) and not np.array_equal(p1, p3)
    assert sorted(p1) == sorted(p3) and sorted(o1) == sorted(o3)
    assert np.allclose(sorted(np.diff(d1, prepend=0)),
                       sorted(np.diff(d3, prepend=0)))
    assert np.isclose(d1[-1], d3[-1])


def test_open_loop_follows_the_file():
    (due, plen, nout), _ = gen.open_loop(SERVE, 3, 40)
    n = len(due)
    assert np.all(np.diff(due) > 0)
    # mean gap 1 / rate
    assert np.mean(np.diff(due, prepend=0)) == pytest.approx(
        1 / SERVE["rate_per_s"], rel=0.05)
    assert set(plen) <= set(SERVE["prompt_lengths"])
    share = np.mean(plen == SERVE["prompt_lengths"][0])
    assert share == pytest.approx(SERVE["prompt_weights"][0], abs=1 / n)
    assert nout.min() >= SERVE["output_min"]
    assert nout.max() <= SERVE["output_max"]
    assert np.median(nout) == pytest.approx(SERVE["output_median"], abs=2)


def _pool(seed):
    return jax.jit(lambda k: gen.token_pool(
        jax, k, shape=(2, 3, 4, 2, 16), vocab=50304, alpha=0.1,
        domains=8))(common.seed_key(jax, seed, 3))


def test_token_pool_repeats_and_differs():
    a, b, c = _pool(5), _pool(5), _pool(BIG)
    assert np.array_equal(a["tokens"], b["tokens"])
    assert not np.array_equal(a["tokens"], c["tokens"])
    toks = np.asarray(a["tokens"])
    assert toks.shape == (2, 3, 4, 2, 16) and toks.dtype == np.int32
    assert toks.min() >= 0 and toks.max() < 50304
    # targets are the next tokens
    assert np.array_equal(np.asarray(a["targets"])[..., :-1], toks[..., 1:])


def test_token_pool_is_non_iid():
    """With alpha = 0.1 each agent's tokens sit mostly in a few of the
    eight domain ranges."""
    p = jax.jit(lambda k: gen.token_pool(
        jax, k, shape=(1, 4, 64, 64), vocab=50304, alpha=0.1,
        domains=8))(common.seed_key(jax, 9, 3))
    toks = np.asarray(p["tokens"])[0]
    for agent in toks:
        dom = np.bincount((agent.ravel() * 8) // 50304, minlength=8)
        assert np.sort(dom)[-2:].sum() > 0.6 * dom.sum()
