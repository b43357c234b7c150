"""Jit'd public wrappers around the Pallas kernels.

Interpret mode is decided by the platform (``repro.kernels.interpret_mode``):
the kernels compile to Mosaic on a TPU and run in the Pallas interpreter
elsewhere. Model code keeps ``use_pallas=False`` by default so the same
graph lowers for the CPU dry-run client.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels.flash_attention import flash_attention_bh


@partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k"))
def flash_attention(q, k, v, *, causal=True, window=None, block_q=128,
                    block_k=128):
    """q: (B,S,H,hd); k,v: (B,S,Kv,hd) with H % Kv == 0 (GQA expanded here).

    Returns (B,S,H,hd)."""
    B, S, H, hd = q.shape
    Kv = k.shape[2]
    if Kv != H:
        rep = H // Kv
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    qb = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kb = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vb = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    out = flash_attention_bh(qb, kb, vb, causal=causal, window=window,
                             block_q=block_q, block_k=block_k)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)


@partial(jax.jit, static_argnames=("block_d",))
def gossip_mix(W, params_stacked, *, block_d=512):
    """Kernel-backed Theta <- W Theta over an agent-stacked pytree.

    Flattening goes through the PanelSpec engine (core/panel.py): leaves are
    grouped by dtype, so a bf16+f32 pytree mixes as one kernel call per
    dtype group with NO silent promotion (the old ``jnp.concatenate`` over
    all leaves upcast everything to the widest dtype, doubling wire bytes).
    """
    from repro.core import panel as panel_mod
    spec = panel_mod.make_spec(params_stacked)
    panel = panel_mod.to_panel(params_stacked, spec)
    mixed = panel_mod.mix_dense(panel, W, use_pallas=True, block_d=block_d)
    return panel_mod.from_panel(mixed, spec)


@partial(jax.jit, static_argnames=("block_d",))
def panel_stats(params_stacked, *, block_d=512):
    """Kernel-backed fused panel statistics over an agent-stacked pytree:
    (merged f32 pytree, consensus distance Xi). One panel_reduce kernel
    call per dtype group — single pass over the parameters."""
    from repro.core import panel as panel_mod
    from repro.kernels.panel_reduce import panel_mean_consensus
    spec = panel_mod.make_spec(params_stacked)
    panel = panel_mod.to_panel(params_stacked, spec)
    m = next(iter(panel.values())).shape[0]
    means = {}
    total = jnp.zeros((), jnp.float32)
    for k, x in panel.items():
        mean, sq = panel_mean_consensus(x, block_d=block_d)
        means[k] = mean
        total = total + sq
    merged = panel_mod.from_panel(means, spec, cast=False)
    return merged, jnp.sqrt(total / m)
