"""The OLMo family (arXiv:2402.00838): everything of the benchmark that
depends on the architecture. A configuration file whose ``model_type`` is
``olmo`` is run through this module (``bench.common.family``).

- The program's side: its ``ModelConfig`` for a configuration file, and
  the benchmark's weights moved into the program's parameter tree and
  back (no copies).
- The float32 reference in ``jax.numpy``, importing nothing of the
  program: the weights from the seed, the forward pass and the loss.
- The counts: parameters held, the matmul parameters every token
  multiplies, attention FLOPs, and cache bytes per position.

Weights are in one layout: ``embed`` (V_padded, d) and the per-layer
matrices stacked over a leading layer axis.

Departures from the published description, each because the program
runs that way and the configuration file records it:

- LayerNorm epsilon 1e-6 (OLMo: 1e-5); non-parametric, as published.
- The tied head projects to the padded vocabulary (a multiple of 256,
  50432 for OLMo's 50304) and the training softmax runs over every
  padded column; serving masks the padding before the argmax.
- Initial weights are normal with standard deviation 1/sqrt(fan_in)
  (the embedding 1/sqrt(d_model)), not OLMo's initialisation.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from bench.reference import matmul

# keys every configuration file of this family holds
CONFIG_KEYS = ("program_arch", "hidden_size", "intermediate_size",
               "num_attention_heads", "num_key_value_heads",
               "num_hidden_layers", "vocab_size", "padded_vocab_size",
               "max_position_embeddings", "layer_norm_eps", "rope_theta")
LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_in", "w_out")
# the published sizes of each source this family's configuration files
# cite; a file departs from them only in its ``reduced`` keys, and its
# ``published`` block keeps their published values
PUBLISHED = {"https://huggingface.co/allenai/OLMo-1B-hf": {
    "hidden_size": 2048, "intermediate_size": 8192,
    "num_attention_heads": 16, "num_key_value_heads": 16,
    "num_hidden_layers": 16, "vocab_size": 50304}}


# --------------------------------------------------------------- program


def program_config(cfg):
    """The program's ``ModelConfig`` for a configuration file: the
    registered architecture with the file's sizes."""
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
    if mc.padded_vocab != cfg["padded_vocab_size"]:
        raise ValueError(f"the program pads the vocabulary to "
                         f"{mc.padded_vocab}, the file says "
                         f"{cfg['padded_vocab_size']}")
    return mc


def to_program(p):
    """Benchmark layout -> the program's parameter tree (no copies)."""
    mixer = {k: p[k] for k in ("wq", "wk", "wv", "wo")}
    ffn = {"w_gate": p["w_gate"], "w_in": p["w_in"], "w_out": p["w_out"]}
    return {"embed": {"table": p["embed"]}, "final_norm": {},
            "decoder": {"main": {"p0": {"norm1": {}, "mixer": mixer,
                                        "norm2": {}, "ffn": ffn}}}}


def from_program(tree):
    """The program's parameter tree -> benchmark layout."""
    blk = tree["decoder"]["main"]["p0"]
    out = {"embed": tree["embed"]["table"]}
    out.update(blk["mixer"])
    out.update(blk["ffn"])
    return out


# ------------------------------------------------------------- reference


def padded_vocab(cfg):
    return cfg["padded_vocab_size"]


def shapes(cfg):
    """{name: shape} of every weight in the benchmark's layout."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    L = cfg["num_hidden_layers"]
    return {"embed": (padded_vocab(cfg), d),
            "wq": (L, d, h * hd), "wk": (L, d, kv * hd),
            "wv": (L, d, kv * hd), "wo": (L, h * hd, d),
            "w_gate": (L, d, f), "w_in": (L, d, f), "w_out": (L, f, d)}


def make_params(cfg, key):
    """Every weight from ``key`` (call inside one jit)."""
    out = {}
    for i, (name, shp) in enumerate(sorted(shapes(cfg).items())):
        fan_in = shp[-1] if name == "embed" else shp[-2]
        out[name] = (jax.random.normal(jax.random.fold_in(key, i), shp,
                                       jnp.float32) / np.sqrt(fan_in))
    return out


def layer_norm(x, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps)


def rope(x, positions, theta):
    """x: (B, S, H, hd); rotates the two halves of each head (GPT-NeoX
    layout, as OLMo)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float32) / hd))
    ang = positions[:, :, None, None].astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang).astype(x.dtype), jnp.sin(ang).astype(x.dtype)
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(cfg, x, p, positions, mm=matmul):
    """One decoder layer: pre-LN causal self-attention and SwiGLU MLP,
    each added to the residual stream. ``mm`` multiplies activations by
    weights."""
    B, S, d = x.shape
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = d // h
    eps = cfg["layer_norm_eps"]
    a = layer_norm(x, eps)
    q = rope(mm(a, p["wq"]).reshape(B, S, h, hd), positions,
             cfg["rope_theta"])
    k = rope(mm(a, p["wk"]).reshape(B, S, kv, hd), positions,
             cfg["rope_theta"])
    v = mm(a, p["wv"]).reshape(B, S, kv, hd)
    rep = h // kv
    k = jnp.repeat(k, rep, axis=2)
    v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(hd).astype(x.dtype)
    causal = np.tril(np.ones((S, S), bool))
    s = jnp.where(causal, s, jnp.asarray(-1e30 if x.dtype == jnp.float32
                                         else -3e38, x.dtype))
    w = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(B, S, h * hd)
    x = x + mm(o, p["wo"])
    a = layer_norm(x, eps)
    return x + mm(jax.nn.silu(mm(a, p["w_gate"])) * mm(a, p["w_in"]),
                  p["w_out"])


def hidden(cfg, params, tokens, mm=matmul):
    """Final-normed hidden states (B, S, d), layer after layer."""
    dt = params["embed"].dtype
    x = params["embed"][tokens]
    B, S = tokens.shape
    pos = jnp.broadcast_to(jnp.arange(S), (B, S))
    layers = {k: params[k] for k in LAYER_KEYS}

    def body(x, p):
        return block(cfg, x, p, pos, mm).astype(dt), None

    x, _ = jax.lax.scan(body, x, layers)
    return layer_norm(x, cfg["layer_norm_eps"])


def logits(cfg, params, tokens, mm=matmul):
    """(B, S, V_padded) logits of the tied head."""
    return mm(hidden(cfg, params, tokens, mm), params["embed"].T)


def loss(cfg, params, tokens, targets, mm=matmul):
    """Mean next-token cross-entropy over every position, softmax over the
    padded vocabulary."""
    lg = logits(cfg, params, tokens, mm)
    lse = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


# ---------------------------------------------------------------- counts


def head_dim(cfg):
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def layer_matmul_params(cfg):
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    hd = head_dim(cfg)
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return d * q + 2 * d * kv + q * d + 3 * d * f


def matmul_params(cfg):
    """Weights that multiply every token: the layers and the tied head
    over the padded vocabulary (the embedding lookup does no
    arithmetic)."""
    return (cfg["num_hidden_layers"] * layer_matmul_params(cfg)
            + cfg["hidden_size"] * cfg["padded_vocab_size"])


def params(cfg):
    """Every parameter the program holds (the tied table counted once)."""
    return matmul_params(cfg)


def attn_flops(cfg, positions):
    """Forward attention FLOPs of all layers for one token that attends to
    ``positions`` positions (itself included): per layer 2 * heads *
    head_dim multiply-adds per position for the scores and as many for
    the values."""
    return (4 * cfg["num_attention_heads"] * head_dim(cfg) * positions
            * cfg["num_hidden_layers"])


def cache_bytes_per_position(cfg, nbytes):
    """Cache bytes of one position at ``nbytes`` per element: the keys and
    values of every layer."""
    return (2 * cfg["num_hidden_layers"] * cfg["num_key_value_heads"]
            * head_dim(cfg) * nbytes)
