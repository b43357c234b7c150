"""Shared neural-net building blocks (pure-jnp, vmap-friendly).

Every ``init_*`` has a matching ``spec_*`` returning a logical-axis tree of
identical structure (asserted by tests for every architecture).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.sharding import constrain
from repro.models.sharding import logical as L


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------


def dense_init(rng, d_in, d_out, dtype=jnp.float32, scale=None):
    scale = scale if scale is not None else 1.0 / np.sqrt(d_in)
    return (jax.random.normal(rng, (d_in, d_out), dtype=jnp.float32)
            * scale).astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------


def init_norm(norm_kind: str, d: int, dtype=jnp.float32):
    if norm_kind == "rmsnorm":
        return {"scale": jnp.ones((d,), dtype)}
    if norm_kind == "layernorm":
        return {"scale": jnp.ones((d,), dtype), "bias": jnp.zeros((d,), dtype)}
    if norm_kind == "nonparam_ln":
        return {}
    raise ValueError(norm_kind)


def spec_norm(norm_kind: str):
    if norm_kind == "rmsnorm":
        return {"scale": L(None)}
    if norm_kind == "layernorm":
        return {"scale": L(None), "bias": L(None)}
    return {}


def apply_norm(params, x, norm_kind: str, eps=1e-6):
    dt = x.dtype
    x = x.astype(jnp.float32)
    if norm_kind == "rmsnorm":
        x = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps)
        x = x * params["scale"].astype(jnp.float32)
    else:
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
        x = (x - mu) * jax.lax.rsqrt(var + eps)
        if norm_kind == "layernorm":
            x = x * params["scale"].astype(jnp.float32) + params["bias"].astype(jnp.float32)
    return x.astype(dt)


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


# ---------------------------------------------------------------------------
# rotary embeddings
# ---------------------------------------------------------------------------


def rope_freqs(head_dim: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x, positions, theta=10000.0):
    """x: (..., S, H, hd); positions: broadcastable to (..., S)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    angles = angles[..., None, :]  # (..., S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x, positions3, sections, theta=1000000.0):
    """Multimodal RoPE (Qwen2-VL). positions3: (3, ..., S) t/h/w position ids;
    ``sections`` splits the hd/2 frequency dims into (t, h, w) groups."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (hd/2,)
    # build per-dim positions by section
    sec = jnp.concatenate([jnp.full((s,), i, dtype=jnp.int32)
                           for i, s in enumerate(sections)])  # (hd/2,)
    # positions3: (3, B, S) -> select per freq-dim
    pos = jnp.take(positions3, sec, axis=0)  # (hd/2, B, S)
    pos = jnp.moveaxis(pos, 0, -1)  # (B, S, hd/2)
    angles = pos.astype(jnp.float32) * freqs  # (B, S, hd/2)
    angles = angles[..., None, :]  # (B, S, 1, hd/2)
    cos, sin = jnp.cos(angles), jnp.sin(angles)
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# dense matmuls
# ---------------------------------------------------------------------------


def dense(x, w):
    """``x @ w`` for a weight consumed only as a dense matmul's right
    operand. A float32 activation against a bfloat16 weight (the serving
    engine's operand copy, ``Model.operand_params``) multiplies bfloat16
    operands with float32 accumulation and returns float32: on a TPU at
    the default precision the product of two float32 operands, made
    without rounding the weight again. Any other pair is ``x @ w``."""
    if x.dtype == jnp.float32 and w.dtype == jnp.bfloat16:
        return jnp.matmul(x.astype(w.dtype), w, preferred_element_type=x.dtype)
    return x @ w


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------


def init_mlp(rng, d, d_ff, gated=True, dtype=jnp.float32):
    ks = jax.random.split(rng, 3)
    p = {"w_in": dense_init(ks[0], d, d_ff, dtype),
         "w_out": dense_init(ks[1], d_ff, d, dtype)}
    if gated:
        p["w_gate"] = dense_init(ks[2], d, d_ff, dtype)
    return p


def spec_mlp(gated=True):
    p = {"w_in": L("fsdp", "model"), "w_out": L("model", "fsdp")}
    if gated:
        p["w_gate"] = L("fsdp", "model")
    return p


def apply_mlp(params, x, act_fn, gated=True):
    h = dense(x, params["w_in"])
    h = constrain(h, ("fsdp", None, "model"))
    if gated:
        h = act_fn(dense(x, params["w_gate"])) * h
    else:
        h = act_fn(h)
    return dense(h, params["w_out"])


# ---------------------------------------------------------------------------
# embeddings + chunked cross-entropy
# ---------------------------------------------------------------------------


def init_embed(rng, vocab, d, dtype=jnp.float32):
    return {"table": (jax.random.normal(rng, (vocab, d), jnp.float32)
                      * (1.0 / np.sqrt(d))).astype(dtype)}


def spec_embed():
    return {"table": L("fsdp", "model")}


def embed_tokens(params, tokens, scale=False):
    x = jnp.take(params["table"], tokens, axis=0)
    if scale:
        x = x * jnp.asarray(np.sqrt(params["table"].shape[-1]), x.dtype)
    return x


def logits_fn(head_w, h):
    """h: (..., d); head_w: (d, V)."""
    return h @ head_w


def chunked_softmax_xent(h, head_w, targets, mask, chunk: int):
    """Cross-entropy without materialising (B,S,V) logits.

    h: (B, S, d); head_w: (d, V); targets: (B, S) int32; mask: (B, S) {0,1}.
    Scans over S in chunks, computing per-chunk logits -> logsumexp -> nll.
    Returns (sum_nll, sum_mask).
    """
    B, S, d = h.shape
    chunk = min(chunk, S)
    n = S // chunk
    rem = S - n * chunk

    def chunk_loss(hc, tc, mc):
        lg = (hc @ head_w).astype(jnp.float32)  # (B, c, V)
        lg = constrain(lg, ("fsdp", None, "model"))
        lse = jax.nn.logsumexp(lg, axis=-1)
        tgt = jnp.take_along_axis(lg, tc[..., None], axis=-1)[..., 0]
        return jnp.sum((lse - tgt) * mc)

    def body(carry, xs):
        hc, tc, mc = xs
        return carry + chunk_loss(hc, tc, mc), None

    hs = h[:, : n * chunk].reshape(B, n, chunk, d).swapaxes(0, 1)
    ts = targets[:, : n * chunk].reshape(B, n, chunk).swapaxes(0, 1)
    ms = mask[:, : n * chunk].reshape(B, n, chunk).swapaxes(0, 1)
    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32), (hs, ts, ms))
    if rem:
        total = total + chunk_loss(h[:, n * chunk:], targets[:, n * chunk:],
                                   mask[:, n * chunk:])
    return total, jnp.sum(mask.astype(jnp.float32))
