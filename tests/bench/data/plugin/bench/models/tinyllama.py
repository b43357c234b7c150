"""A second model family for the harness's tests, written as a change
that adds a model would add one: a Llama-style decoder (RMSNorm with
learned scales, grouped-query attention, SwiGLU, RoPE) with an untied
head, run through the program's ``yi-34b`` architecture at the
configuration file's sizes. Its parameter tree differs from OLMo's: norm
scales in every layer and at the end, and a head of its own."""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import matmul

CONFIG_KEYS = ("program_arch", "hidden_size", "intermediate_size",
               "num_attention_heads", "num_key_value_heads",
               "num_hidden_layers", "vocab_size", "padded_vocab_size",
               "max_position_embeddings", "rms_norm_eps", "rope_theta")
LAYER_KEYS = ("norm1", "wq", "wk", "wv", "wo", "norm2", "w_gate", "w_in",
              "w_out")
PUBLISHED = {"https://example.org/tinyllama-test-family": {
    "hidden_size": 32, "intermediate_size": 64, "num_attention_heads": 2,
    "num_key_value_heads": 1, "num_hidden_layers": 2, "vocab_size": 250}}


def program_config(cfg):
    from repro.configs import get_config
    base = get_config(cfg["program_arch"])
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    attn = dataclasses.replace(base.attn, num_heads=h,
                               num_kv_heads=cfg["num_key_value_heads"],
                               head_dim=d // h, rope_theta=cfg["rope_theta"])
    mc = base.replace(num_layers=cfg["num_hidden_layers"], d_model=d,
                      d_ff=cfg["intermediate_size"],
                      vocab_size=cfg["vocab_size"], attn=attn,
                      max_seq_len=cfg["max_position_embeddings"])
    assert mc.padded_vocab == cfg["padded_vocab_size"]
    assert not mc.tie_embeddings and mc.norm == "rmsnorm"
    return mc


def to_program(p):
    return {"embed": {"table": p["embed"]},
            "final_norm": {"scale": p["final_norm"]},
            "head": {"w": p["head"]},
            "decoder": {"main": {"p0": {
                "norm1": {"scale": p["norm1"]},
                "mixer": {k: p[k] for k in ("wq", "wk", "wv", "wo")},
                "norm2": {"scale": p["norm2"]},
                "ffn": {k: p[k] for k in ("w_gate", "w_in", "w_out")}}}}}


def from_program(tree):
    blk = tree["decoder"]["main"]["p0"]
    out = {"embed": tree["embed"]["table"],
           "final_norm": tree["final_norm"]["scale"],
           "head": tree["head"]["w"], "norm1": blk["norm1"]["scale"],
           "norm2": blk["norm2"]["scale"]}
    out.update(blk["mixer"])
    out.update(blk["ffn"])
    return out


def shapes(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, L, V = d // h, cfg["num_hidden_layers"], cfg["padded_vocab_size"]
    return {"embed": (V, d), "head": (d, V), "final_norm": (d,),
            "norm1": (L, d), "norm2": (L, d),
            "wq": (L, d, h * hd), "wk": (L, d, kv * hd),
            "wv": (L, d, kv * hd), "wo": (L, h * hd, d),
            "w_gate": (L, d, f), "w_in": (L, d, f), "w_out": (L, f, d)}


def make_params(cfg, key):
    out = {}
    for i, (name, shp) in enumerate(sorted(shapes(cfg).items())):
        z = jax.random.normal(jax.random.fold_in(key, i), shp, jnp.float32)
        if "norm" in name:
            out[name] = 1.0 + 0.1 * z
        else:
            fan_in = shp[-1] if name == "embed" else shp[-2]
            out[name] = z / np.sqrt(fan_in)
    return out


def rms_norm(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                        + eps) * scale


def rope(x, positions, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = positions[:, :, None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(cfg, x, p, positions, mm=matmul):
    B, S, d = x.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, eps = d // h, cfg["rms_norm_eps"]
    a = rms_norm(x, p["norm1"], eps)
    q = rope(mm(a, p["wq"]).reshape(B, S, h, hd), positions,
             cfg["rope_theta"])
    k = rope(mm(a, p["wk"]).reshape(B, S, kv, hd), positions,
             cfg["rope_theta"])
    v = mm(a, p["wv"]).reshape(B, S, kv, hd)
    k = jnp.repeat(k, h // kv, axis=2)
    v = jnp.repeat(v, h // kv, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd).astype(x.dtype)
    s = jnp.where(np.tril(np.ones((S, S), bool)), s,
                  jnp.asarray(-1e30 if x.dtype == jnp.float32 else -3e38,
                              x.dtype))
    o = jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, axis=-1),
                   v).reshape(B, S, h * hd)
    x = x + mm(o, p["wo"])
    a = rms_norm(x, p["norm2"], eps)
    return x + mm(jax.nn.silu(mm(a, p["w_gate"])) * mm(a, p["w_in"]),
                  p["w_out"])


def hidden(cfg, params, tokens, mm=matmul):
    dt = params["embed"].dtype
    x = params["embed"][tokens]
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))

    def body(x, p):
        return block(cfg, x, p, pos, mm).astype(dt), None

    x, _ = jax.lax.scan(body, x, {k: params[k] for k in LAYER_KEYS})
    return rms_norm(x, params["final_norm"], cfg["rms_norm_eps"])


def logits(cfg, params, tokens, mm=matmul):
    return mm(hidden(cfg, params, tokens, mm), params["head"])


def loss(cfg, params, tokens, targets, mm=matmul):
    lg = logits(cfg, params, tokens, mm)
    lse = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def layer_matmul_params(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * f


def matmul_params(cfg):
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["padded_vocab_size"])


def params(cfg):
    d = cfg["hidden_size"]
    return (matmul_params(cfg) + d * cfg["padded_vocab_size"]
            + (2 * cfg["num_hidden_layers"] + 1) * d)


def attn_flops(cfg, positions):
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return (4 * cfg["num_attention_heads"] * hd * positions
            * cfg["num_hidden_layers"])


def cache_bytes_per_position(cfg, nbytes):
    hd = cfg["hidden_size"] // cfg["num_attention_heads"]
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"] * hd
            * nbytes)
