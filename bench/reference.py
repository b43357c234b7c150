"""Plain reference of OLMo (arXiv:2402.00838) in ``jax.numpy``: the
forward pass, the loss and its gradients, AdamW, the gossip mix
``W @ Theta``, and full-sequence logits for serving.

It imports nothing of the program. Weights come from :func:`make_params`
(the benchmark makes them from the seed and hands the same arrays to the
program), in one layout: ``embed`` (V_padded, d) and the per-layer
matrices stacked over a leading layer axis.

Float32 runs under ``jax.default_matmul_precision("highest")``. Lower
precisions give the controls the checks must reject: every weight and
activation in bfloat16, or float8 e4m3 operands in every weight matmul
and bfloat16 elsewhere.

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

import jax
import jax.numpy as jnp
import numpy as np

LAYER_KEYS = ("wq", "wk", "wv", "wo", "w_gate", "w_in", "w_out")


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


def matmul(a, w):
    return a @ w


def fp8_matmul(a, w):
    """Both operands rounded to float8 e4m3, products summed in float32."""
    f8 = jnp.float8_e4m3fn
    return jnp.matmul(a.astype(f8), w.astype(f8),
                      preferred_element_type=jnp.float32).astype(a.dtype)


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


def loss(cfg, params, tokens, targets):
    """Mean next-token cross-entropy over every position, softmax over the
    padded vocabulary."""
    lg = logits(cfg, params, tokens)
    lse = jax.nn.logsumexp(lg, axis=-1)
    tgt = jnp.take_along_axis(lg, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt)


def cast(params, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), params)


# ------------------------------------------------------------- training


def agent_grads(cfg, dtype):
    """(stacked params, stacked batches) -> (losses (m,), stacked grads
    in float32), agent after agent."""
    def one(xs):
        p, tok, tgt = xs
        lval, g = jax.value_and_grad(
            lambda q: loss(cfg, cast(q, dtype), tok, tgt))(p)
        return lval.astype(jnp.float32), g

    def run(params, tokens, targets):
        return jax.lax.map(one, (params, tokens, targets))
    return run


def adamw(params, grads, m, v, count, *, lr, b1, b2, eps, wd,
          moment_dtype):
    """Decoupled AdamW, moments kept in ``moment_dtype``."""
    c = count.astype(jnp.float32)
    bc1, bc2 = 1 - b1 ** c, 1 - b2 ** c

    def one(p, g, m_, v_):
        m_ = b1 * m_.astype(jnp.float32) + (1 - b1) * g
        v_ = b2 * v_.astype(jnp.float32) + (1 - b2) * jnp.square(g)
        p = p - lr * ((m_ / bc1) / (jnp.sqrt(v_ / bc2) + eps) + wd * p)
        return p, m_.astype(moment_dtype), v_.astype(moment_dtype)

    out = jax.tree.map(one, params, grads, m, v)
    tr = lambda i: jax.tree.map(lambda t: t[i], out,  # noqa: E731
                                is_leaf=lambda t: isinstance(t, tuple))
    return tr(0), tr(1), tr(2)


def mix(W, params):
    """Theta <- W @ Theta over the agent axis of every leaf."""
    return jax.tree.map(
        lambda x: jnp.einsum("ij,j...->i...", W, x, precision="highest"),
        params)


def consensus(params):
    """Xi = sqrt(mean_k ||theta_k - mean||^2) over all leaves."""
    tot = 0.0
    for x in jax.tree.leaves(params):
        tot = tot + jnp.sum(jnp.square(x - jnp.mean(x, 0, keepdims=True)))
    m = jax.tree.leaves(params)[0].shape[0]
    return jnp.sqrt(tot / m)


def tree_norm(tree):
    return jnp.sqrt(sum(jnp.sum(jnp.square(x)) for x in jax.tree.leaves(tree)))


# -------------------------------------------------------------- serving


def next_tokens(seq):
    """The token each position of ``seq`` (B, T) is followed by."""
    return jnp.concatenate([seq[:, 1:], seq[:, :1]], axis=1)


def gaps(cfg):
    """(params, seq (B, T), cand (B, T), first (B,), n (B,)) -> (gaps
    (B, T), rows (B, V)): at the positions first .. first+n-1 of each row,
    the float32 reference's best logit minus its logit of ``cand`` there
    (0 elsewhere); and its logits at position ``first``, the last prompt
    token, which prefill answers. Logits are masked to the real
    vocabulary, as the server samples."""
    V = cfg["vocab_size"]

    def run(params, seq, cand, first, n):
        lg = logits(cfg, params, seq)[..., :V]
        t = jnp.arange(seq.shape[1])[None]
        valid = (t >= first[:, None]) & (t < (first + n)[:, None])
        got = jnp.take_along_axis(lg, jnp.minimum(cand, V - 1)[..., None],
                                  -1)[..., 0]
        row = jnp.take_along_axis(lg, first[:, None, None], 1)[:, 0]
        return jnp.where(valid, jnp.max(lg, -1) - got, 0.0), row
    return run


def picks(cfg, dtype, mm=matmul):
    """(params, seq (B, T), first (B,)) -> (argmax (B, T), rows (B, V)):
    the argmax over the real vocabulary at every position, and the
    logits at position ``first``, computed in ``dtype`` with ``mm`` for
    the weight matmuls."""
    V = cfg["vocab_size"]

    def run(params, seq, first):
        lg = logits(cfg, cast(params, dtype), seq, mm)[..., :V]
        row = jnp.take_along_axis(lg, first[:, None, None], 1)[:, 0]
        return (jnp.argmax(lg, -1).astype(jnp.int32),
                row.astype(jnp.float32))
    return run
