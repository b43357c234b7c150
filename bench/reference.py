"""Plain reference in ``jax.numpy``: the loss and its gradients, AdamW,
the gossip mix ``W @ Theta``, and full-sequence logits for serving.

It imports nothing of the program. What depends on the architecture (the
weights from the seed, the forward pass and the loss) is the family
module's that the configuration file's ``model_type`` names
(``bench/models/<model_type>.py``, found by ``bench.common.family``);
this module holds what every family shares. Weights come from
:func:`make_params` (the benchmark makes them from the seed and hands the
same arrays to the program).

Float32 runs under ``jax.default_matmul_precision("highest")``. Lower
precisions give the controls the checks must reject: every weight and
activation in bfloat16, or float8 e4m3 operands in every weight matmul
and bfloat16 elsewhere.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench import common


def matmul(a, w):
    return a @ w


def fp8_matmul(a, w):
    """Both operands rounded to float8 e4m3, products summed in float32."""
    f8 = jnp.float8_e4m3fn
    return jnp.matmul(a.astype(f8), w.astype(f8),
                      preferred_element_type=jnp.float32).astype(a.dtype)


def make_params(cfg, key):
    """Every weight from ``key`` (call inside one jit)."""
    return common.family(cfg).make_params(cfg, key)


def logits(cfg, params, tokens, mm=matmul):
    """(B, S, V_padded) logits; ``mm`` multiplies activations by
    weights."""
    return common.family(cfg).logits(cfg, params, tokens, mm)


def loss(cfg, params, tokens, targets, mm=matmul):
    """Mean next-token cross-entropy over every position."""
    return common.family(cfg).loss(cfg, params, tokens, targets, mm)


def cast(params, dtype):
    return jax.tree.map(lambda x: x.astype(dtype), params)


# ------------------------------------------------------------- training


def agent_grads(cfg, dtype, rows=None):
    """(stacked params, stacked batches) -> (losses (m,), stacked grads
    in float32), agent after agent, an agent's batch ``rows`` sequences at
    a time (so that it fits; the whole batch where ``rows`` is None or not
    under it), the loss and gradients the mean over those equal blocks.
    A single block is added to zero and divided by one, both exact."""
    def grad(p, tok, tgt):
        return jax.value_and_grad(
            lambda q: loss(cfg, cast(q, dtype), tok, tgt))(p)

    def one(xs):
        p, tok, tgt = xs
        B = tok.shape[0]
        r = B if rows is None else min(rows, B)
        if B % r:
            raise ValueError(f"a batch of {B} in blocks of {r}")
        n = B // r

        def block(acc, xs):
            lval, g = grad(p, *xs)
            return jax.tree.map(jnp.add, acc, (lval, g)), None
        zero = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, p))
        (lval, g), _ = jax.lax.scan(
            block, zero, (tok.reshape((n, r) + tok.shape[1:]),
                          tgt.reshape((n, r) + tgt.shape[1:])))
        return lval / n, jax.tree.map(lambda x: x / n, g)

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
