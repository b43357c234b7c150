"""Attention mixers: GQA/MQA (optional sliding window), MLA, cross-attention.

All functions are pure-jnp and vmap-friendly. Decode uses a unified cache
layout ``{"k","v","pos"}`` where ``pos`` stores the absolute position of each
cache slot (-1 = empty); sliding-window archs allocate only ``window`` slots
and write round-robin, so ``long_500k`` caches stay O(window).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import AttentionConfig, LayerSpec, ModelConfig
from repro.models.layers import (apply_mrope, apply_norm, apply_rope, dense,
                                 dense_init)
from repro.models.sharding import constrain, constrain_pick
from repro.models.sharding import logical as L

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# GQA / MQA
# ---------------------------------------------------------------------------


def init_gqa(rng, cfg: ModelConfig, dtype=jnp.float32):
    a = cfg.attn
    ks = jax.random.split(rng, 4)
    return {
        "wq": dense_init(ks[0], cfg.d_model, a.q_dim, dtype),
        "wk": dense_init(ks[1], cfg.d_model, a.kv_dim, dtype),
        "wv": dense_init(ks[2], cfg.d_model, a.kv_dim, dtype),
        "wo": dense_init(ks[3], a.q_dim, cfg.d_model, dtype),
    }


def spec_gqa():
    return {"wq": L("fsdp", "model"), "wk": L("fsdp", "model"),
            "wv": L("fsdp", "model"), "wo": L("model", "fsdp")}


def _rope_q_or_k(x, positions, a: AttentionConfig, positions3=None):
    if a.rope == "rope":
        return apply_rope(x, positions, a.rope_theta)
    if a.rope == "mrope":
        return apply_mrope(x, positions3, a.mrope_sections, a.rope_theta)
    return x


def _mask_bias(q_pos, k_pos, *, causal: bool, window: Optional[int]):
    """q_pos: (..., Sq); k_pos: (..., Sk) -> additive bias (..., Sq, Sk)."""
    qp = q_pos[..., :, None]
    kp = k_pos[..., None, :]
    ok = kp >= 0
    if causal:
        ok &= kp <= qp
    if window is not None:
        ok &= kp > qp - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def _sdpa(q, k, v, bias, scale):
    """q: (B,Sq,H,dq) k: (B,Sk,Kv,dq) v: (B,Sk,Kv,dv) bias: (B,Sq,Sk).

    Returns (B,Sq,H,dv); dq may differ from dv (MLA)."""
    B, Sq, H, dq = q.shape
    Kv = k.shape[2]
    dv = v.shape[-1]
    G = H // Kv
    q = q.reshape(B, Sq, Kv, G, dq)
    # shard the score tensor (B,Kv,G,Sq,Sk): kv-heads, else q-groups, else
    # the query-sequence dim (MQA with few heads)
    _fixed = [(-5, "fsdp")]
    _pick = [(-4, "model"), (-3, "model"), (-2, "model")]
    scores = jnp.einsum("bqkgd,bskd->bkgqs", q, k).astype(jnp.float32) * scale
    scores = constrain_pick(scores, _fixed, _pick)
    scores = scores + bias[:, None, None, :, :]
    attn = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    attn = constrain_pick(attn, _fixed, _pick)
    out = jnp.einsum("bkgqs,bskd->bqkgd", attn, v)  # (B,Sq,Kv,G,dv)
    out = constrain_pick(out, [(-5, "fsdp")],
                         [(-3, "model"), (-2, "model"), (-1, "model")])
    return out.reshape(B, Sq, H, dv)


def _sdpa_blockwise(q, k, v, q_pos, k_pos, *, causal, window, scale,
                    block: int):
    """Flash-style online-softmax attention in pure XLA: scans KV in chunks
    of ``block`` so the (Sq, Sk) score matrix is never materialised — the
    jnp twin of kernels/flash_attention.py used by the dry-run/train path.

    q: (B,Sq,H,dq) k/v: (B,Sk,Kv,dv). Returns (B,Sq,H,dv)."""
    B, Sq, H, dq = q.shape
    Sk, Kv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    G = H // Kv
    block = min(block, Sk)
    pad = (-Sk) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
    nb = (Sk + pad) // block
    qr = q.reshape(B, Sq, Kv, G, dq)

    def body(carry, xs):
        m_run, l_run, acc = carry
        kb, vb, kpb = xs  # (B,block,Kv,d), (B,block)
        s = jnp.einsum("bqkgd,bskd->bkgqs", qr, kb).astype(jnp.float32)
        s = s * scale
        ok = kpb[:, None, None, None, :] >= 0
        if causal:
            ok &= kpb[:, None, None, None, :] <= q_pos[:, None, None, :, None]
        if window is not None:
            ok &= (kpb[:, None, None, None, :]
                   > q_pos[:, None, None, :, None] - window)
        s = jnp.where(ok, s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_run - m_new)
        l_new = alpha * l_run + jnp.sum(p, axis=-1)
        acc = (acc * alpha[..., None]
               + jnp.einsum("bkgqs,bskd->bkgqd", p.astype(vb.dtype),
                            vb).astype(jnp.float32))
        return (m_new, l_new, acc), None

    m0 = jnp.full((B, Kv, G, Sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Kv, G, Sq), jnp.float32)
    a0 = jnp.zeros((B, Kv, G, Sq, dv), jnp.float32)

    def split(t):
        return jnp.moveaxis(
            t.reshape(B, nb, block, *t.shape[2:]), 1, 0)

    (m_f, l_f, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      (split(k), split(v), split(k_pos)))
    out = (acc / jnp.maximum(l_f, 1e-30)[..., None]).astype(v.dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, Sq, H, dv)


def gqa_forward(params, x, *, cfg: ModelConfig, lspec: LayerSpec,
                positions, mode: str, cache=None, index=None,
                positions3=None, causal=True, cache_max_len=None):
    """Returns (y, new_cache). mode in {"train","prefill","decode"}."""
    a = cfg.attn
    B, S, _ = x.shape
    _hpick = [(-2, "model"), (-1, "model")]
    q = dense(x, params["wq"]).reshape(B, S, a.num_heads, a.head_dim)
    k = dense(x, params["wk"]).reshape(B, S, a.num_kv_heads, a.head_dim)
    v = dense(x, params["wv"]).reshape(B, S, a.num_kv_heads, a.head_dim)
    q = constrain_pick(q, [(-4, "fsdp")], _hpick)
    k = constrain_pick(k, [(-4, "fsdp")], _hpick)
    v = constrain_pick(v, [(-4, "fsdp")], _hpick)
    q = _rope_q_or_k(q, positions, a, positions3)
    k = _rope_q_or_k(k, positions, a, positions3)
    scale = 1.0 / np.sqrt(a.head_dim)

    if mode == "decode":
        # single-step: S == 1; write (k,v) into the cache ring/linear buffer.
        # Each batch row writes at its OWN absolute position (``positions``
        # is (B, 1)): under continuous batching every slot sits at a
        # different depth, so the write is a per-row scatter, not a shared
        # dynamic_update_slice.
        W = cache["k"].shape[1]
        idx = positions[:, 0].astype(jnp.int32)  # (B,) absolute positions
        slots = jnp.mod(idx, W)
        rows = jnp.arange(B)
        ck = cache["k"].at[rows, slots].set(k[:, 0])
        cv = cache["v"].at[rows, slots].set(v[:, 0])
        cpos = cache["pos"].at[rows, slots].set(idx)
        bias = _mask_bias(positions, cpos, causal=causal, window=lspec.window)
        y = _sdpa(q, ck, cv, bias, scale)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
    else:
        pos_b = jnp.broadcast_to(positions, (B, S))
        if cfg.dist.attn_block:
            y = _sdpa_blockwise(q, k, v, pos_b, pos_b, causal=causal,
                                window=lspec.window, scale=scale,
                                block=cfg.dist.attn_block)
        else:
            bias = _mask_bias(pos_b, pos_b, causal=causal,
                              window=lspec.window)
            y = _sdpa(q, k, v, bias, scale)
        new_cache = None
        if mode == "prefill":
            new_cache = _prefill_cache(cfg, lspec, k, v, positions, B, S,
                                       cache_max_len or S)

    y = dense(y.reshape(B, S, a.q_dim), params["wo"])
    return y, new_cache


def _prefill_cache(cfg, lspec, k, v, positions, B, S, max_len):
    W = cache_len(cfg, lspec, max_len)
    pos = jnp.broadcast_to(positions, (B, S)).astype(jnp.int32)
    if W >= S:
        pad = W - S
        ck = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        cpos = jnp.pad(pos, ((0, 0), (0, pad)), constant_values=-1)
    else:
        # keep the trailing window, laid out so slot = pos % W (ring buffer)
        tail_k, tail_v = k[:, S - W:], v[:, S - W:]
        tail_p = pos[:, S - W:]
        slots = jnp.mod(tail_p[0], W)  # same for all batch rows
        inv = jnp.argsort(slots)
        ck, cv, cpos = tail_k[:, inv], tail_v[:, inv], tail_p[:, inv]
    return {"k": ck, "v": cv, "pos": cpos}


def cache_len(cfg: ModelConfig, lspec: LayerSpec, seq_len: int) -> int:
    return min(lspec.window, seq_len) if lspec.window else seq_len


def init_gqa_cache(cfg: ModelConfig, lspec: LayerSpec, B: int, seq_len: int,
                   dtype=jnp.float32):
    a = cfg.attn
    W = cache_len(cfg, lspec, seq_len)
    return {"k": jnp.zeros((B, W, a.num_kv_heads, a.head_dim), dtype),
            "v": jnp.zeros((B, W, a.num_kv_heads, a.head_dim), dtype),
            "pos": jnp.full((B, W), -1, jnp.int32)}


def spec_gqa_cache():
    return {"k": L("data", None, "model", None),
            "v": L("data", None, "model", None),
            "pos": L("data", None)}


# ---------------------------------------------------------------------------
# MLA (DeepSeek-V3)
# ---------------------------------------------------------------------------


def init_mla(rng, cfg: ModelConfig, dtype=jnp.float32):
    a = cfg.attn
    ks = jax.random.split(rng, 5)
    H = a.num_heads
    return {
        "wq_a": dense_init(ks[0], cfg.d_model, a.q_lora_rank, dtype),
        "q_norm": {"scale": jnp.ones((a.q_lora_rank,), dtype)},
        "wq_b": dense_init(ks[1], a.q_lora_rank,
                           H * (a.qk_nope_dim + a.qk_rope_dim), dtype),
        "wkv_a": dense_init(ks[2], cfg.d_model,
                            a.kv_lora_rank + a.qk_rope_dim, dtype),
        "kv_norm": {"scale": jnp.ones((a.kv_lora_rank,), dtype)},
        "wkv_b": dense_init(ks[3], a.kv_lora_rank,
                            H * (a.qk_nope_dim + a.v_head_dim), dtype),
        "wo": dense_init(ks[4], H * a.v_head_dim, cfg.d_model, dtype),
    }


def spec_mla():
    return {"wq_a": L("fsdp", None), "q_norm": {"scale": L(None)},
            "wq_b": L(None, "model"), "wkv_a": L("fsdp", None),
            "kv_norm": {"scale": L(None)}, "wkv_b": L(None, "model"),
            "wo": L("model", "fsdp")}


def _mla_qkr(params, x, a, positions):
    B, S, _ = x.shape
    H = a.num_heads
    ql = apply_norm(params["q_norm"], x @ params["wq_a"], "rmsnorm")
    q = (ql @ params["wq_b"]).reshape(B, S, H, a.qk_nope_dim + a.qk_rope_dim)
    q_nope, q_rope = jnp.split(q, [a.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, a.rope_theta)
    kv = x @ params["wkv_a"]
    ckv, k_rope = jnp.split(kv, [a.kv_lora_rank], axis=-1)
    ckv = apply_norm(params["kv_norm"], ckv, "rmsnorm")
    k_rope = apply_rope(k_rope[:, :, None, :], positions, a.rope_theta)[:, :, 0]
    return q_nope, q_rope, ckv, k_rope


def mla_forward(params, x, *, cfg: ModelConfig, lspec: LayerSpec, positions,
                mode: str, cache=None, index=None, cache_max_len=None, **_):
    a = cfg.attn
    B, S, _ = x.shape
    H = a.num_heads
    q_nope, q_rope, ckv, k_rope = _mla_qkr(params, x, a, positions)
    scale = 1.0 / np.sqrt(a.qk_nope_dim + a.qk_rope_dim)
    wkv_b = params["wkv_b"].reshape(a.kv_lora_rank, H,
                                    a.qk_nope_dim + a.v_head_dim)
    wk = wkv_b[..., : a.qk_nope_dim]  # (r, H, dn)
    wv = wkv_b[..., a.qk_nope_dim:]  # (r, H, dv)

    if mode == "decode":
        W = cache["ckv"].shape[1]
        idx = positions[:, 0].astype(jnp.int32)  # (B,) per-slot positions
        slots = jnp.mod(idx, W)
        rows = jnp.arange(B)
        cc = cache["ckv"].at[rows, slots].set(ckv[:, 0])
        cr = cache["krope"].at[rows, slots].set(k_rope[:, 0])
        cpos = cache["pos"].at[rows, slots].set(idx)
        bias = _mask_bias(positions, cpos, causal=True, window=lspec.window)
        # absorbed attention: scores in latent space
        q_lat = jnp.einsum("bqhd,rhd->bqhr", q_nope, wk)
        scores = (jnp.einsum("bqhr,bsr->bhqs", q_lat, cc)
                  + jnp.einsum("bqhd,bsd->bhqs", q_rope, cr)).astype(jnp.float32)
        scores = constrain_pick(scores, [(-4, "fsdp")],
                                [(-3, "model"), (-1, "model")])
        scores = scores * scale + bias[:, None, :, :]
        attn = jax.nn.softmax(scores, axis=-1).astype(cc.dtype)
        o_lat = jnp.einsum("bhqs,bsr->bqhr", attn, cc)
        out = jnp.einsum("bqhr,rhd->bqhd", o_lat, wv)
        new_cache = {"ckv": cc, "krope": cr, "pos": cpos}
    else:
        # materialised form for train/prefill
        k_nope = jnp.einsum("bsr,rhd->bshd", ckv, wk)
        k_nope = constrain_pick(k_nope, [(-4, "fsdp")], [(-2, "model")])
        v = jnp.einsum("bsr,rhd->bshd", ckv, wv)
        v = constrain_pick(v, [(-4, "fsdp")], [(-2, "model")])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                      (B, S, H, a.qk_rope_dim))], axis=-1)
        q = jnp.concatenate([q_nope, q_rope], axis=-1)
        pos_b = jnp.broadcast_to(positions, (B, S))
        bias = _mask_bias(pos_b, pos_b, causal=True, window=lspec.window)
        out = _sdpa(q, k, v, bias, scale)
        new_cache = None
        if mode == "prefill":
            W = cache_max_len or S
            pad = max(0, W - S)
            new_cache = {
                "ckv": jnp.pad(ckv, ((0, 0), (0, pad), (0, 0))),
                "krope": jnp.pad(k_rope, ((0, 0), (0, pad), (0, 0))),
                "pos": jnp.pad(pos_b.astype(jnp.int32), ((0, 0), (0, pad)),
                               constant_values=-1)}
    y = out.reshape(B, S, H * a.v_head_dim) @ params["wo"]
    return y, new_cache


def init_mla_cache(cfg: ModelConfig, lspec: LayerSpec, B: int, seq_len: int,
                   dtype=jnp.float32):
    a = cfg.attn
    return {"ckv": jnp.zeros((B, seq_len, a.kv_lora_rank), dtype),
            "krope": jnp.zeros((B, seq_len, a.qk_rope_dim), dtype),
            "pos": jnp.full((B, seq_len), -1, jnp.int32)}


def spec_mla_cache():
    return {"ckv": L("data", None, None), "krope": L("data", None, None),
            "pos": L("data", None)}


# ---------------------------------------------------------------------------
# Cross-attention (encoder-decoder)
# ---------------------------------------------------------------------------


def init_cross(rng, cfg: ModelConfig, dtype=jnp.float32):
    a = cfg.attn
    ks = jax.random.split(rng, 4)
    return {"wq": dense_init(ks[0], cfg.d_model, a.q_dim, dtype),
            "wk": dense_init(ks[1], cfg.d_model, a.kv_dim, dtype),
            "wv": dense_init(ks[2], cfg.d_model, a.kv_dim, dtype),
            "wo": dense_init(ks[3], a.q_dim, cfg.d_model, dtype)}


spec_cross = spec_gqa


def cross_kv(params, enc_out, *, cfg: ModelConfig):
    """Project encoder output once; cached across decode steps.

    Carries a ``pos`` row (-1 = empty) so a cache row padded to a larger
    encoder capacity (slotted serving: rows are spliced into a
    max_len-sized buffer) keeps its padding masked out."""
    a = cfg.attn
    B, Se, _ = enc_out.shape
    k = dense(enc_out, params["wk"]).reshape(B, Se, a.num_kv_heads,
                                             a.head_dim)
    v = dense(enc_out, params["wv"]).reshape(B, Se, a.num_kv_heads,
                                             a.head_dim)
    pos = jnp.broadcast_to(jnp.arange(Se, dtype=jnp.int32), (B, Se))
    return {"k": k, "v": v, "pos": pos}


def cross_forward(params, x, kv, *, cfg: ModelConfig):
    """Full (non-causal) attention from decoder states to cached enc K/V."""
    a = cfg.attn
    B, S, _ = x.shape
    Se = kv["k"].shape[1]
    q = dense(x, params["wq"]).reshape(B, S, a.num_heads, a.head_dim)
    if "pos" in kv:  # mask padded encoder slots (pos == -1)
        bias = jnp.where(kv["pos"][:, None, :] >= 0, 0.0, NEG_INF
                         ).astype(jnp.float32)
        bias = jnp.broadcast_to(bias, (B, S, Se))
    else:
        bias = jnp.zeros((B, S, Se), jnp.float32)
    y = _sdpa(q, kv["k"], kv["v"], bias, 1.0 / np.sqrt(a.head_dim))
    return dense(y.reshape(B, S, a.q_dim), params["wo"])
