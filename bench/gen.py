"""Traffic generators, driven only by a traffic file's parameters and the
seed.

- :func:`token_pool`: the non-IID training stream. Each agent draws a
  domain mixture from Dirichlet(alpha); each sequence a domain from its
  agent's mixture; tokens follow the program's structured domain chain
  (``repro.data.synthetic.SyntheticLM`` above its dense-table vocabulary,
  copied here so the yardstick cannot move), vectorised on the device.
- :func:`open_loop`: serving requests with fixed sizes and arrival gaps.
  Every seed gets the same schedule of prompt lengths, output lengths and
  arrivals, and prompts of its own, so seeds never change the work.
"""
from __future__ import annotations

import math

import numpy as np
from scipy.special import ndtri

# SyntheticLM's structured chain
CHAIN_STEP = 7919
CHAIN_JITTER = 8
ORDER_SKEW = 4.0


def token_pool(jax, key, *, shape, vocab, alpha, domains):
    """int32 tokens and targets of ``shape`` + (seq,), ``shape`` =
    (..., m, b) with the agent axis second to last. One jitted call."""
    jnp = jax.numpy
    *lead, m, b, seq = shape
    n = int(np.prod(lead)) if lead else 1
    k_mix, k_dom, k_first, k_stay, k_jit, k_uni = jax.random.split(key, 6)
    mixtures = jax.random.dirichlet(k_mix, jnp.full((domains,), alpha),
                                    (m,))
    # (n, m, b) rows: each picks a domain from its agent's mixture
    dom = jax.random.categorical(
        k_dom, jnp.log(mixtures)[None, :, None, :], shape=(n, m, b))
    lo = (dom * vocab) // domains
    size = ((dom + 1) * vocab) // domains - lo
    per = vocab / domains
    stay = ORDER_SKEW * per / (ORDER_SKEW * per + 0.05 * (vocab - per))
    w = 1.0 / np.arange(1, CHAIN_JITTER + 1)
    logp_jit = jnp.asarray(np.log(w / w.sum()), jnp.float32)
    first = jax.random.randint(k_first, (n, m, b), 0, vocab)
    steps = jnp.arange(seq)

    def body(tok, t):
        u = jax.random.uniform(jax.random.fold_in(k_stay, t), tok.shape)
        jit = jax.random.categorical(jax.random.fold_in(k_jit, t), logp_jit,
                                     shape=tok.shape)
        uni = jax.random.randint(jax.random.fold_in(k_uni, t), tok.shape, 0,
                                 vocab)
        inside = lo + (tok * CHAIN_STEP + jit) % size
        nxt = jnp.where(u < stay, inside, uni).astype(jnp.int32)
        return nxt, nxt

    _, rest = jax.lax.scan(body, first.astype(jnp.int32), steps)
    toks = jnp.concatenate([first[None].astype(jnp.int32), rest], 0)
    toks = jnp.moveaxis(toks, 0, -1).reshape(tuple(lead) + (m, b, seq + 1))
    return {"tokens": toks[..., :-1], "targets": toks[..., 1:]}


def _quantiles(n):
    return (np.arange(n) + 0.5) / n


def open_loop(traffic, seed, seconds):
    """The requests due in a window of ``seconds``: (due_s, prompt_len,
    max_new) arrays, sorted by due time, plus a numpy Generator, drawn
    from ``seed``, for the prompts' tokens. The round(rate * seconds)
    requests, all due inside the window, are quantiles of the mix's
    distributions (gaps of the exponential, scaled to end inside the
    window), put in the order that the traffic file's ``order_seed``
    draws: near the knee the order alone moves a tail several-fold, so
    every seed gets the same schedule and other prompts."""
    rate = float(traffic["rate_per_s"])
    if traffic["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrival']!r}")
    n = max(1, int(round(rate * seconds)))
    order = np.random.default_rng(int(traffic["order_seed"]))
    q = _quantiles(n)
    gaps = -np.log1p(-q) / rate
    gaps *= seconds * (1 - 0.5 / n) / gaps.sum()
    lens = np.asarray(traffic["prompt_lengths"])
    wts = np.asarray(traffic["prompt_weights"], float)
    counts = np.floor(wts / wts.sum() * n).astype(int)
    for i in np.argsort(-(wts / wts.sum() * n - counts))[:n - counts.sum()]:
        counts[i] += 1
    prompt = np.repeat(lens, counts)
    out = np.exp(np.log(traffic["output_median"])
                 + traffic["output_sigma"] * ndtri(q))
    out = np.clip(np.rint(out), traffic["output_min"],
                  traffic["output_max"]).astype(int)
    due = np.cumsum(order.permutation(gaps))
    sizes = order.permutation(n)
    return ((due, prompt[sizes], order.permutation(out)),
            np.random.default_rng(int(seed)))
