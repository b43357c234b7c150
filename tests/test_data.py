"""Data pipeline: Dirichlet partitioning + synthetic generators."""
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container: property tests skip gracefully
    from _hypothesis_stub import given, settings, strategies as st

from repro.data.dirichlet import dirichlet_partition, heterogeneity
from repro.data.synthetic import (SyntheticClassification, SyntheticLM,
                                  make_agent_batches, make_agent_lm_batches)


@given(m=st.sampled_from([2, 8, 16]), alpha=st.sampled_from([0.1, 1.0, 10.0]),
       seed=st.integers(0, 50))
@settings(max_examples=20, deadline=None)
def test_partition_covers_all_examples_once(m, alpha, seed):
    rng = np.random.default_rng(seed)
    labels = rng.integers(0, 10, size=500)
    parts = dirichlet_partition(labels, m, alpha, rng, min_per_agent=0)
    allidx = np.concatenate([p for p in parts if len(p)])
    assert len(allidx) == 500
    assert len(np.unique(allidx)) == 500


def test_small_alpha_more_heterogeneous():
    rng = np.random.default_rng(0)
    labels = rng.integers(0, 10, size=4000)
    h_small = np.mean([heterogeneity(
        dirichlet_partition(labels, 8, 0.1, np.random.default_rng(s)),
        labels, 10) for s in range(5)])
    h_big = np.mean([heterogeneity(
        dirichlet_partition(labels, 8, 100.0, np.random.default_rng(s)),
        labels, 10) for s in range(5)])
    assert h_small > h_big + 0.2


def test_classification_batches_shapes():
    ds = SyntheticClassification(n_train=512, n_test=128)
    parts = ds.partition(4, 0.1)
    xb, yb = make_agent_batches(ds, parts, 16, np.random.default_rng(0))
    assert xb.shape == (4, 16, ds.dim) and yb.shape == (4, 16)


def test_lm_domain_skew_changes_statistics():
    lm = SyntheticLM(vocab=64, num_domains=4, seed=0)
    rng = np.random.default_rng(0)
    d0 = lm.sample(np.array([1.0, 0, 0, 0]), 64, 64, rng)
    d3 = lm.sample(np.array([0, 0, 0, 1.0]), 64, 64, rng)
    h0 = np.bincount(d0.ravel(), minlength=64) / d0.size
    h3 = np.bincount(d3.ravel(), minlength=64) / d3.size
    tv = 0.5 * np.abs(h0 - h3).sum()
    assert tv > 0.3  # clearly different token distributions


def test_lm_large_vocab_is_structured_and_domain_skewed():
    """Above DENSE_MAX_VOCAB no transition table is built; each domain's
    streams still sit mostly in its own token subset."""
    V, D = 50304, 8
    lm = SyntheticLM(vocab=V, num_domains=D, seed=0)
    assert lm._trans is None
    rng = np.random.default_rng(0)
    for d in (0, D - 1):
        toks = lm.sample(np.eye(D)[d], 8, 128, rng)
        assert toks.shape == (8, 129) and toks.dtype == np.int32
        assert (toks >= 0).all() and (toks < V).all()
        lo, hi = d * V // D, (d + 1) * V // D
        inside = np.mean((toks[:, 1:] >= lo) & (toks[:, 1:] < hi))
        assert inside > 0.8  # the dense tables' expected mass is 0.92


def test_lm_agent_batches_structure():
    lm = SyntheticLM(vocab=32, num_domains=4)
    mix = lm.domain_mixtures(3, 0.1)
    b = make_agent_lm_batches(lm, mix, 4, 16, np.random.default_rng(0))
    assert b["tokens"].shape == (3, 4, 16)
    assert (b["targets"][:, :, :-1] == b["tokens"][:, :, 1:]).all()
