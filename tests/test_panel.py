"""Flat-panel engine validation: PanelSpec dtype preservation, fused-op
parity against the per-leaf tree-map reference path, Pallas panel_reduce
kernel vs oracle, the donated scanned segment driver, and state
panelize/unpanelize roundtrips."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dsgd, gossip, topology
from repro.core import panel as panel_mod
from repro.core.consensus import consensus_distance, consensus_distance_tree
from repro.optim import make_optimizer


def _mixed_tree(m=8, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    return {"w": jax.random.normal(ks[0], (m, 17, 5)),
            "emb": jax.random.normal(ks[1], (m, 33), jnp.bfloat16),
            "nest": {"b": jax.random.normal(ks[2], (m, 9))}}


# ------------------------------------------------------------ spec/panel


def test_spec_preserves_mixed_dtypes_no_promotion():
    """Regression for kernels/ops.py:_flatten_panel: a bf16+f32 pytree must
    flatten into per-dtype panels with NO silent upcast (the old
    jnp.concatenate promoted bf16 leaves to f32, doubling wire bytes)."""
    tree = _mixed_tree()
    spec = panel_mod.make_spec(tree)
    pan = panel_mod.to_panel(tree, spec)
    assert set(pan) == {"float32", "bfloat16"}
    assert pan["bfloat16"].dtype == jnp.bfloat16
    assert pan["float32"].dtype == jnp.float32
    assert pan["bfloat16"].shape == (8, 33)
    assert pan["float32"].shape == (8, 17 * 5 + 9)
    # wire bytes: bf16 leaves pay 2 bytes, not 4
    promoted = spec.width * 4
    assert spec.wire_bytes == 33 * 2 + (17 * 5 + 9) * 4 < promoted


def test_panel_roundtrip_exact():
    tree = _mixed_tree()
    spec = panel_mod.make_spec(tree)
    back = panel_mod.from_panel(panel_mod.to_panel(tree, spec), spec)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(back)):
        assert a.dtype == b.dtype and a.shape == b.shape
        assert bool(jnp.all(a == b))


def test_gossip_mix_kernel_preserves_dtypes():
    """ops.gossip_mix on a mixed-dtype pytree: one kernel call per dtype
    group, output dtypes unchanged."""
    from repro.kernels.ops import gossip_mix
    tree = _mixed_tree()
    W = jnp.asarray(topology.ring(8), jnp.float32)
    out = gossip_mix(W, tree)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(out)):
        assert a.dtype == b.dtype and a.shape == b.shape
    ref = gossip.mix_dense_tree(tree, W)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(ref)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=2e-2, rtol=2e-2)


# ------------------------------------------------ fused ops vs tree path


@pytest.mark.parametrize("wire", [None, jnp.bfloat16])
def test_mix_dense_panel_matches_tree(wire):
    tree = {"x": jax.random.normal(jax.random.PRNGKey(1), (8, 40)),
            "y": jax.random.normal(jax.random.PRNGKey(2), (8, 7, 3))}
    W = jnp.asarray(topology.random_matching(
        8, 0.7, np.random.default_rng(0)), jnp.float32)
    a = gossip.mix_dense(tree, W, wire_dtype=wire)
    b = gossip.mix_dense_tree(tree, W, wire_dtype=wire)
    tol = 2e-2 if wire is not None else 1e-5
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(x, y, atol=tol, rtol=tol)


def test_mix_pairwise_panel_matches_tree():
    m = 8
    W = topology.random_matching(m, 0.8, np.random.default_rng(3))
    partner = jnp.asarray(topology.partner_array(W), jnp.int32)
    tree = {"x": jax.random.normal(jax.random.PRNGKey(3), (m, 13))}
    a = gossip.mix_pairwise(tree, partner)
    b = gossip.mix_pairwise_tree(tree, partner)
    np.testing.assert_allclose(a["x"], b["x"], atol=1e-6)


def test_global_merge_and_merged_model_mixed_dtype():
    """Acceptance: the panel engine's merged model matches
    gossip.global_merge within f32 tolerance on a MIXED-dtype pytree."""
    tree = _mixed_tree(seed=4)
    gm_p = gossip.global_merge(tree)
    gm_t = gossip.global_merge_tree(tree)
    for a, b in zip(jax.tree.leaves(gm_p), jax.tree.leaves(gm_t)):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   atol=1e-5, rtol=1e-5)
    mm_p = gossip.merged_model(tree)
    mm_t = gossip.merged_model_tree(tree)
    for a, b in zip(jax.tree.leaves(mm_p), jax.tree.leaves(mm_t)):
        assert a.dtype == jnp.float32  # merged model is f32 in both engines
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)


def test_consensus_distance_panel_matches_tree():
    tree = _mixed_tree(seed=5)
    a = float(consensus_distance(tree))
    b = float(consensus_distance_tree(tree))
    assert a == pytest.approx(b, rel=1e-5)


# ------------------------------------------------------ panel_reduce kernel


@pytest.mark.parametrize("m,D,block_d", [
    (4, 64, 32), (8, 1000, 512), (16, 333, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_panel_reduce_kernel_vs_ref(m, D, block_d, dtype):
    from repro.kernels.panel_reduce import panel_mean_consensus
    from repro.kernels.ref import panel_mean_consensus_ref
    theta = jax.random.normal(jax.random.PRNGKey(6), (m, D), dtype)
    mean, sq = panel_mean_consensus(theta, block_d=block_d)
    rmean, rsq = panel_mean_consensus_ref(theta)
    np.testing.assert_allclose(mean, rmean, atol=1e-5, rtol=1e-5)
    assert float(sq) == pytest.approx(float(rsq), rel=1e-5)


def test_panel_stats_wrapper():
    from repro.kernels.ops import panel_stats
    tree = _mixed_tree(seed=7)
    merged, xi = panel_stats(tree)
    ref = gossip.merged_model_tree(tree)
    for a, b in zip(jax.tree.leaves(merged), jax.tree.leaves(ref)):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-5)
    assert float(xi) == pytest.approx(
        float(consensus_distance_tree(tree)), rel=1e-5)


def test_consensus_distance_pallas_path():
    tree = {"x": jax.random.normal(jax.random.PRNGKey(8), (8, 700))}
    spec = panel_mod.make_spec(tree)
    pan = panel_mod.to_panel(tree, spec)
    a = float(panel_mod.consensus_distance(pan, use_pallas=True))
    b = float(consensus_distance_tree(tree))
    assert a == pytest.approx(b, rel=1e-5)


# ------------------------------------------------------ segment driver


def _toy_problem(m=8, dim=12, classes=4):
    def init_params(rng):
        k1, _ = jax.random.split(rng)
        return {"w": jax.random.normal(k1, (dim, classes)) * 0.1,
                "b": jnp.zeros(classes)}

    def loss_fn(p, batch, rng=None):
        x, y = batch
        lg = x @ p["w"] + p["b"]
        nll = jnp.mean(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, y[:, None], -1)[:, 0])
        return nll, {}

    return init_params, loss_fn


def _segment_inputs(S, H, m, dim, classes, seed=0):
    rng = np.random.default_rng(seed)
    Ws = np.stack([topology.random_matching(m, 0.5, rng) for _ in range(S)])
    bx = jnp.asarray(rng.normal(size=(S, H, m, 8, dim)).astype(np.float32))
    by = jnp.asarray(rng.integers(0, classes, size=(S, H, m, 8)).astype(np.int32))
    return jnp.asarray(Ws, jnp.float32), (bx, by)


@pytest.mark.parametrize("opt_name", ["sgd", "adamw"])
def test_panel_segment_matches_tree_rounds(opt_name):
    """The donated scanned segment must reproduce the tree-state round
    driver exactly (same rng schedule, same batches, same W sequence)."""
    m, H, S, dim, classes = 8, 3, 4, 12, 4
    init_params, loss_fn = _toy_problem(m, dim, classes)
    opt = make_optimizer(opt_name, 1e-2)
    key = jax.random.PRNGKey(0)
    tstate = dsgd.init_state(init_params, opt, m, key)
    pstate, spec = dsgd.init_panel_state(init_params, opt, m, key)
    round_fn = jax.jit(dsgd.make_dsgd_round(loss_fn, opt, H))
    seg_fn = dsgd.make_panel_segment(loss_fn, opt, H, spec)

    Ws, (bx, by) = _segment_inputs(S, H, m, dim, classes)
    key2 = jax.random.PRNGKey(42)
    rngs = jax.random.split(key2, S)
    ts = tstate
    for t in range(S):
        ts, mets_t = round_fn(ts, (bx[t], by[t]), Ws[t], rngs[t])
    ps, mets_p = seg_fn(pstate, (bx, by), Ws, key2)

    final = panel_mod.from_panel(ps["panel"], spec)
    for a, b in zip(jax.tree.leaves(final), jax.tree.leaves(ts["params"])):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)
    assert mets_p["loss"].shape == (S,)
    assert float(mets_p["loss"][-1]) == pytest.approx(
        float(mets_t["loss"]), rel=1e-5)
    assert float(mets_p["consensus"][-1]) == pytest.approx(
        float(mets_t["consensus"]), rel=1e-4)
    assert int(ps["step"]) == S * H


def test_panel_segment_donates_state():
    """The scanned round must NOT retain the old state buffer: with
    donate_argnums the input panels are consumed in place."""
    m, H, S, dim, classes = 4, 2, 2, 6, 3
    init_params, loss_fn = _toy_problem(m, dim, classes)
    opt = make_optimizer("sgd", 1e-2)
    # probe: does this backend actually delete donated buffers?
    probe = jnp.ones((4,))
    jax.jit(lambda x: x * 2, donate_argnums=(0,))(probe)
    if not probe.is_deleted():
        pytest.skip("backend does not implement buffer donation")
    pstate, spec = dsgd.init_panel_state(init_params, opt, m,
                                         jax.random.PRNGKey(0))
    seg_fn = dsgd.make_panel_segment(loss_fn, opt, H, spec)
    Ws, batches = _segment_inputs(S, H, m, dim, classes)
    old_bufs = jax.tree.leaves(pstate)
    new_state, _ = seg_fn(pstate, batches, Ws, jax.random.PRNGKey(1))
    assert all(x.is_deleted() for x in old_bufs)
    assert not any(x.is_deleted() for x in jax.tree.leaves(new_state))


def test_panel_segment_final_merge_collapses_consensus():
    m, H, dim, classes = 8, 2, 10, 3
    init_params, loss_fn = _toy_problem(m, dim, classes)
    opt = make_optimizer("sgd", 1e-2)
    pstate, spec = dsgd.init_panel_state(init_params, opt, m,
                                         jax.random.PRNGKey(0))
    seg_fn = dsgd.make_panel_segment(loss_fn, opt, H, spec)
    rng = np.random.default_rng(0)
    Ws = np.stack([topology.random_matching(m, 0.5, rng),
                   topology.fully_connected(m)])
    bx = jnp.asarray(rng.normal(size=(2, H, m, 8, dim)).astype(np.float32))
    by = jnp.asarray(rng.integers(0, classes, size=(2, H, m, 8)).astype(np.int32))
    ps, mets = seg_fn(pstate, (bx, by), jnp.asarray(Ws, jnp.float32),
                      jax.random.PRNGKey(1))
    assert float(mets["consensus"][-1]) < 1e-3  # global merge => Xi ~ 0
    tree = panel_mod.from_panel(ps["panel"], spec)
    for x in jax.tree.leaves(tree):
        np.testing.assert_allclose(np.asarray(x[0]), np.asarray(x[-1]),
                                   atol=1e-5)


def test_panel_segment_idle_rounds_ignore_wire_dtype():
    """W == I rounds communicate nothing, so a bf16 wire must not quantize
    them: local-only training is bitwise identical under any wire dtype."""
    m, H, S, dim, classes = 4, 2, 3, 8, 3
    init_params, loss_fn = _toy_problem(m, dim, classes)
    opt = make_optimizer("sgd", 1e-2)
    Ws = jnp.asarray(np.stack([topology.identity(m)] * S), jnp.float32)
    rng = np.random.default_rng(1)
    bx = jnp.asarray(rng.normal(size=(S, H, m, 8, dim)).astype(np.float32))
    by = jnp.asarray(rng.integers(0, classes, size=(S, H, m, 8)).astype(np.int32))
    finals = []
    for wire in (None, jnp.bfloat16):
        pstate, spec = dsgd.init_panel_state(init_params, opt, m,
                                             jax.random.PRNGKey(0))
        seg_fn = dsgd.make_panel_segment(loss_fn, opt, H, spec,
                                         wire_dtype=wire)
        ps, _ = seg_fn(pstate, (bx, by), Ws, jax.random.PRNGKey(1))
        finals.append(ps["panel"])
    for a, b in zip(jax.tree.leaves(finals[0]), jax.tree.leaves(finals[1])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_panelize_unpanelize_roundtrip():
    m = 4
    init_params, _ = _toy_problem(m)
    opt = make_optimizer("adamw", 1e-3)
    key = jax.random.PRNGKey(2)
    tstate = dsgd.init_state(init_params, opt, m, key)
    spec = panel_mod.make_spec(tstate["params"])
    ps = dsgd.panelize_state(tstate, spec)
    back = dsgd.unpanelize_state(ps, spec)
    for a, b in zip(jax.tree.leaves(tstate), jax.tree.leaves(back)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # and the panel init path agrees with panelizing the tree init
    pstate, spec2 = dsgd.init_panel_state(init_params, opt, m, key)
    assert spec2 == spec
    for a, b in zip(jax.tree.leaves(pstate["panel"]),
                    jax.tree.leaves(ps["panel"])):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ------------------------------------------------- one-device agent loop


def _row_loop(one, pan, batch, rngs, spec):
    """The agent loop of the row relayout, kept here as the oracle: each
    agent's row is cut out of the (m, D) panel and rebuilt into leaves,
    and its gradient is joined into a row that lax.map stacks."""
    def agent(xs):
        row, b, r = xs
        params = panel_mod.from_panel(row, spec)
        with dsgd.scope("dsgd.fwd_bwd"):
            g, l = one(params, b, r)
        gp = panel_mod.to_panel(jax.tree.map(lambda x: x[None], g), spec)
        return {k: v[0] for k, v in gp.items()}, l

    return jax.lax.map(agent, (pan, batch, rngs))


def _keep_dtypes(opt):
    """``opt`` with params and moments cast back to their stored dtypes,
    so bf16 leaves stay bf16 through the segment's carry."""
    def like(new, old):
        return jax.tree.map(lambda a, b: a.astype(b.dtype), new, old)

    def update(grads, state, params, step=None):
        new_p, new_st = opt.update(grads, state, params, step)
        return like(new_p, params), {
            k: like(v, state[k]) if k in opt.moment_keys else v
            for k, v in new_st.items()}

    return dataclasses.replace(opt, update=update)


def _mixed_lm_segment(monkeypatch, agent_loop=None, residency=None):
    """(compiled segment, its arguments, spec) of a tiny gemma at m = 3
    whose norm scales are stored in bf16: two dtype groups, and leaves of
    96 and 48 scalars, no multiple of 128. ``agent_loop`` stands in for
    ``dsgd._agents_in_turn`` while the segment is traced."""
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("gemma-2b").reduced(d_model=48, vocab=64, layers=2)
    model = build_model(cfg)

    def init_params(rng):
        p = model.init_params(rng)
        blk = p["decoder"]["main"]["p0"]
        for k in ("norm1", "norm2"):
            blk[k]["scale"] = blk[k]["scale"].astype(jnp.bfloat16)
        p["final_norm"]["scale"] = p["final_norm"]["scale"].astype(
            jnp.bfloat16)
        return p

    opt = _keep_dtypes(make_optimizer("adamw", 1e-2))
    m, H, S, B, T = 3, 2, 3, 2, 8
    state, spec = dsgd.init_panel_state(init_params, opt, m,
                                        jax.random.PRNGKey(0),
                                        residency=residency)
    toks = jax.random.randint(jax.random.PRNGKey(3), (S, H, m, B, T + 1), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[..., :-1], "targets": toks[..., 1:],
             "mask": jnp.ones((S, H, m, B, T), jnp.float32)}
    rng = np.random.default_rng(0)
    Ws = jnp.asarray(np.stack([topology.random_matching(m, 0.5, rng),
                               topology.identity(m),
                               topology.fully_connected(m)]), jnp.float32)
    args = (state, batch, Ws, jax.random.PRNGKey(7))
    seg = dsgd.make_panel_segment(model.loss_fn, opt, H, spec)
    with monkeypatch.context() as mp:
        if agent_loop is not None:
            mp.setattr(dsgd, "_agents_in_turn", agent_loop)
        compiled = seg.lower(*args).compile()
    return compiled, args, spec


@pytest.mark.parametrize("residency", [None, "moments=bf16"])
def test_whole_panel_relayout_matches_row_loop(monkeypatch, residency):
    """The one-device agent loop on agent-stacked slabs of the whole panel
    gives the row loop's final segment state and metrics, bit for bit."""
    outs = []
    for loop in (None, _row_loop):
        compiled, args, spec = _mixed_lm_segment(monkeypatch, loop,
                                                 residency)
        outs.append(jax.device_get(compiled(*args)))
    assert {k for k, _ in spec.groups} == {"float32", "bfloat16"}
    assert any(ls.size % 128 for ls in spec.leaves)
    flat = [jax.tree_util.tree_leaves_with_path(o) for o in outs]
    assert len(flat[0]) == len(flat[1])
    for (path, a), (_, b) in zip(*flat):
        np.testing.assert_array_equal(a, b, err_msg=jax.tree_util.keystr(
            path))


_HLO_DTYPE = {"float32": "f32", "bfloat16": "bf16"}


def _panel_slicing_ops(hlo_text, spec):
    """(opcode, instruction) of every dynamic-slice and dynamic-update-slice
    whose first operand is a whole (m, D_g) group panel."""
    shape_of = dict(re.findall(r"%([\w.\-]+) = (\w+\[[\d,]*\])",
                               hlo_text))
    panels = {f"{_HLO_DTYPE[k]}[{spec.rows},{w}]" for k, w in spec.groups}
    ops = re.findall(r"%([\w.\-]+) = \S+ (dynamic-(?:update-)?slice)"
                     r"\(%([\w.\-]+)", hlo_text)
    return [(op, name) for name, op, arg in ops
            if shape_of.get(arg) in panels]


def test_agent_loop_slices_no_whole_panel(monkeypatch):
    """Compiled, the one-device agent loop reads no agent's row out of
    the (m, D) panel and writes none into it: the row loop does both."""
    compiled, _, spec = _mixed_lm_segment(monkeypatch)
    assert _panel_slicing_ops(compiled.as_text(), spec) == []
    compiled, _, _ = _mixed_lm_segment(monkeypatch, _row_loop)
    kinds = {op for op, _ in _panel_slicing_ops(compiled.as_text(), spec)}
    assert kinds == {"dynamic-slice", "dynamic-update-slice"}
