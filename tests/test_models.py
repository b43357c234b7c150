"""Model-component correctness: MoE dispatch vs dense reference, mLSTM
chunkwise vs naive recurrence, RG-LRU scan vs step-by-step, chunked CE vs
direct, rope invariants."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # offline container: property tests skip gracefully
    from _hypothesis_stub import given, settings, strategies as st

from repro.configs import get_config
from repro.configs.base import LayerSpec
from repro.models import moe as moe_mod
from repro.models import recurrent as rec
from repro.models import build_model
from repro.models.layers import (apply_rope, chunked_softmax_xent, dense,
                                 init_mlp, apply_norm, init_norm)


# ---------------------------------------------------------------- MoE


def _moe_cfg(E=4, k=2, cap=10.0):
    cfg = get_config("arctic-480b").reduced(d_model=64, experts=E)
    m = dataclasses.replace(cfg.moe, top_k=k, capacity_factor=cap)
    return cfg.replace(moe=m)


def test_moe_matches_dense_reference_no_drops():
    """With a huge capacity factor no tokens drop; the gather/scatter path
    must equal the dense compute-everything reference."""
    cfg = _moe_cfg(cap=100.0)
    key = jax.random.PRNGKey(0)
    params = moe_mod.init_moe(key, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
    y1, aux1 = moe_mod.moe_forward(params, x, cfg=cfg, act_name=cfg.act)
    y2, aux2 = moe_mod.moe_ref(params, x, cfg=cfg, act_name=cfg.act)
    np.testing.assert_allclose(y1, y2, atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(aux1, aux2, atol=1e-6)


def test_moe_capacity_drops_reduce_output():
    cfg_lo = _moe_cfg(cap=0.25)
    cfg_hi = _moe_cfg(cap=100.0)
    params = moe_mod.init_moe(jax.random.PRNGKey(0), cfg_hi)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, cfg_hi.d_model))
    y_lo, _ = moe_mod.moe_forward(params, x, cfg=cfg_lo, act_name="silu")
    y_hi, _ = moe_mod.moe_forward(params, x, cfg=cfg_hi, act_name="silu")
    # dropped tokens -> some outputs reduced to shared/dense-only part
    assert float(jnp.mean(jnp.abs(y_lo))) < float(jnp.mean(jnp.abs(y_hi)))


def test_moe_aux_loss_balanced_router_is_minimal():
    cfg = _moe_cfg(E=4, k=1)
    E = 4
    T = 64
    # perfectly balanced probs -> aux = E * sum(1/E * 1/E) * E? == 1
    probs = jnp.full((T, E), 1.0 / E)
    # craft via _route: monkey-instance — test the formula directly
    counts = jnp.full((E,), T / E)
    frac = counts / T
    aux = E * jnp.sum(frac * probs.mean(0))
    assert float(aux) == pytest.approx(1.0, abs=1e-5)


def test_deepseek_sigmoid_router_weights_normalised():
    cfg = get_config("deepseek-v3-671b").reduced()
    params = moe_mod.init_moe(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (8, cfg.d_model))
    w, sel, aux = moe_mod._route(x, params, cfg.moe)
    np.testing.assert_allclose(np.asarray(w.sum(-1)), 1.0, atol=1e-5)
    assert sel.shape == (8, cfg.moe.top_k)


# ---------------------------------------------------------------- mLSTM


def _naive_mlstm(q, k, v, logf, logi):
    """Step-by-step stabilised mLSTM recurrence (ground truth)."""
    B, H, S, dh = q.shape
    C = np.zeros((B, H, dh, dh), np.float64)
    n = np.zeros((B, H, dh), np.float64)
    m = np.full((B, H), -1e30, np.float64)
    hs = np.zeros((B, H, S, dh), np.float64)
    q, k, v = (np.asarray(t, np.float64) for t in (q, k, v))
    logf, logi = np.asarray(logf, np.float64), np.asarray(logi, np.float64)
    for t in range(S):
        m_new = np.maximum(logf[..., t] + m, logi[..., t])
        fp = np.exp(logf[..., t] + m - m_new)
        ip = np.exp(logi[..., t] - m_new)
        C = fp[..., None, None] * C + ip[..., None, None] * (
            k[:, :, t, :, None] * v[:, :, t, None, :])
        n = fp[..., None] * n + ip[..., None] * k[:, :, t]
        m = m_new
        num = np.einsum("bhde,bhd->bhe", C, q[:, :, t])
        den = np.maximum(np.abs(np.einsum("bhd,bhd->bh", n, q[:, :, t])),
                         np.exp(-m))
        hs[:, :, t] = num / den[..., None]
    return hs


@pytest.mark.parametrize("S,chunk", [(32, 8), (64, 16), (48, 16)])
def test_mlstm_chunkwise_matches_naive(S, chunk):
    B, H, dh = 2, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(ks[0], (B, H, S, dh))
    k = jax.random.normal(ks[1], (B, H, S, dh))
    v = jax.random.normal(ks[2], (B, H, S, dh))
    logi = jax.random.normal(ks[3], (B, H, S))
    logf = jax.nn.log_sigmoid(jax.random.normal(ks[4], (B, H, S)) + 2.0)

    st0 = (jnp.zeros((B, H, dh, dh)), jnp.zeros((B, H, dh)),
           jnp.full((B, H), -1e30))
    outs = []
    state = st0
    for c0 in range(0, S, chunk):
        sl = slice(c0, c0 + chunk)
        h, state = rec._mlstm_chunk(q[:, :, sl], k[:, :, sl], v[:, :, sl],
                                    logf[:, :, sl], logi[:, :, sl], state)
        outs.append(h)
    got = jnp.concatenate(outs, axis=2)
    want = _naive_mlstm(q, k, v, logf, logi)
    np.testing.assert_allclose(got, want, atol=1e-4, rtol=1e-3)


def test_mlstm_decode_continues_prefill():
    cfg = get_config("xlstm-1.3b").reduced()
    params = rec.init_mlstm(jax.random.PRNGKey(0), cfg)
    B, S = 2, 32
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    full, _ = rec.mlstm_forward(params, x, cfg=cfg, mode="train")
    y, state = rec.mlstm_forward(params, x[:, :S - 1], cfg=cfg,
                                 mode="prefill")
    y2, _ = rec.mlstm_forward(params, x[:, S - 1:], cfg=cfg, mode="decode",
                              state=state)
    np.testing.assert_allclose(y2[:, 0], full[:, -1], atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------- RG-LRU


def test_rglru_decode_matches_scan():
    cfg = get_config("recurrentgemma-2b").reduced()
    params = rec.init_rglru(jax.random.PRNGKey(0), cfg)
    B, S = 2, 16
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    full, _ = rec.rglru_forward(params, x, cfg=cfg, mode="train")
    state = rec.init_rglru_state(cfg, B)
    outs = []
    for t in range(S):
        y, state = rec.rglru_forward(params, x[:, t:t + 1], cfg=cfg,
                                     mode="decode", state=state)
        outs.append(y)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(got, full, atol=1e-4, rtol=1e-3)


def test_slstm_decode_matches_scan():
    cfg = get_config("xlstm-1.3b").reduced()
    params = rec.init_slstm(jax.random.PRNGKey(0), cfg)
    B, S = 2, 12
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model))
    full, _ = rec.slstm_forward(params, x, cfg=cfg, mode="train")
    state = rec.init_slstm_state(cfg, B)
    outs = []
    for t in range(S):
        y, state = rec.slstm_forward(params, x[:, t:t + 1], cfg=cfg,
                                     mode="decode", state=state)
        outs.append(y)
    got = jnp.concatenate(outs, axis=1)
    np.testing.assert_allclose(got, full, atol=1e-4, rtol=1e-3)


# ---------------------------------------------------------------- layers


@given(S=st.sampled_from([16, 33, 64]), chunk=st.sampled_from([7, 16, 64]))
@settings(max_examples=12, deadline=None)
def test_chunked_xent_matches_direct(S, chunk):
    B, d, V = 2, 16, 50
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    h = jax.random.normal(ks[0], (B, S, d))
    w = jax.random.normal(ks[1], (d, V)) * 0.3
    t = jax.random.randint(ks[2], (B, S), 0, V)
    mask = (jnp.arange(S)[None] < S - 2).astype(jnp.float32) * jnp.ones((B, 1))
    nll, cnt = chunked_softmax_xent(h, w, t, mask, chunk)
    lg = (h @ w).astype(jnp.float32)
    ref = jnp.sum((jax.nn.logsumexp(lg, -1) - jnp.take_along_axis(
        lg, t[..., None], -1)[..., 0]) * mask)
    np.testing.assert_allclose(nll, ref, rtol=1e-5, atol=1e-4)
    assert float(cnt) == float(mask.sum())


def test_rope_preserves_norm_and_relativity():
    B, S, H, hd = 1, 8, 1, 16
    x = jax.random.normal(jax.random.PRNGKey(0), (B, S, H, hd))
    pos = jnp.arange(S)[None]
    y = apply_rope(x, pos)
    np.testing.assert_allclose(jnp.linalg.norm(y, axis=-1),
                               jnp.linalg.norm(x, axis=-1), rtol=1e-5)
    # relative property: <rope(q,i), rope(k,j)> depends only on i-j
    q = jax.random.normal(jax.random.PRNGKey(1), (1, 1, 1, hd))
    k = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 1, hd))
    def dot(i, j):
        qi = apply_rope(q, jnp.array([[i]]))
        kj = apply_rope(k, jnp.array([[j]]))
        return float(jnp.sum(qi * kj))
    assert dot(3, 1) == pytest.approx(dot(7, 5), abs=1e-4)


def test_nonparam_ln_has_no_params():
    p = init_norm("nonparam_ln", 16)
    assert p == {}
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 16)) * 5 + 3
    y = apply_norm(p, x, "nonparam_ln")
    np.testing.assert_allclose(jnp.mean(y, -1), 0.0, atol=1e-5)
    np.testing.assert_allclose(jnp.var(y, -1), 1.0, atol=1e-3)


# ---------------------------------------------------------------- dense


@pytest.mark.parametrize("xshape", [(5, 16), (2, 3, 16)])
def test_dense_of_f32_operands_is_plain_matmul(xshape):
    """Float32 weights (every training path) keep ``x @ w``'s jaxpr."""
    x = jnp.ones(xshape, jnp.float32)
    w = jnp.ones((16, 8), jnp.float32)
    assert (str(jax.make_jaxpr(dense)(x, w))
            == str(jax.make_jaxpr(lambda x, w: x @ w)(x, w)))


def test_dense_of_bf16_weight_accumulates_in_f32():
    """A float32 activation against a bfloat16 weight: bfloat16 operands,
    float32 accumulation and result, the weight never promoted back."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 3, 16), jnp.float32)
    w = jax.random.normal(jax.random.PRNGKey(1), (16, 8)).astype(jnp.bfloat16)
    y = dense(x, w)
    assert y.dtype == jnp.float32
    np.testing.assert_array_equal(
        y, jnp.matmul(x.astype(jnp.bfloat16), w,
                      preferred_element_type=jnp.float32))
    jaxpr = jax.make_jaxpr(dense)(x, w).jaxpr
    casts = [e for e in jaxpr.eqns if e.primitive.name == "convert_element_type"]
    assert [c.invars[0].aval.shape for c in casts] == [x.shape]
    xb = x.astype(jnp.bfloat16)  # bf16 activations: unchanged, bf16 out
    assert dense(xb, w).dtype == jnp.bfloat16
    assert (str(jax.make_jaxpr(dense)(xb, w))
            == str(jax.make_jaxpr(lambda x, w: x @ w)(xb, w)))


# leaf names each family's operand_params casts; every other leaf is kept
_OPERANDS = {"wq", "wk", "wv", "wo", "w_in", "w_gate", "w_out"}


@pytest.mark.parametrize("arch,kw,cast_parts", [
    ("olmo-1b", {}, {"decoder/main/p0/mixer", "decoder/main/p0/ffn"}),
    # (rglru, rglru, local attention): the recurrent mixers are kept
    ("recurrentgemma-2b", {"layers": 3},
     {"decoder/main/p0/ffn", "decoder/main/p1/ffn", "decoder/main/p2/ffn",
      "decoder/main/p2/mixer"}),
    ("seamless-m4t-medium", {},
     {"decoder/main/p0/mixer", "decoder/main/p0/cross",
      "decoder/main/p0/ffn", "encoder/main/p0/mixer",
      "encoder/main/p0/ffn"}),
    # MLA everywhere, experts after the dense front layer: only its MLP
    ("deepseek-v3-671b", {"layers": 2}, {"decoder/front/p0/ffn"}),
])
def test_operand_params_casts_only_dense_matmul_operands(arch, kw,
                                                         cast_parts):
    """Attention projections (standard and cross) and dense MLP matrices
    are cast; the embedding, norms, MLA, recurrent cells and expert layers
    keep their dtype; structure and shapes do not change."""
    cfg = get_config(arch).reduced(d_model=64, vocab=64, **kw)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    out = model.operand_params(params, jnp.bfloat16)
    assert jax.tree.structure(out) == jax.tree.structure(params)
    got = dict(jax.tree_util.tree_flatten_with_path(out)[0])
    cast = set()
    for path, leaf in jax.tree_util.tree_flatten_with_path(params)[0]:
        new = got[path]
        assert new.shape == leaf.shape
        if new.dtype == leaf.dtype:
            continue
        assert new.dtype == jnp.bfloat16 and path[-1].key in _OPERANDS
        cast.add("/".join(k.key for k in path[:-1]))
    assert cast == cast_parts
