"""Pallas TPU kernel for gossip parameter mixing: Theta_out = W @ Theta.

The hot loop of the paper's communication step once gathered parameters are
on-chip: a skinny (m x m) mixing matrix applied to a huge (m x D) parameter
panel. TPU adaptation: D is tiled into MXU-aligned VMEM blocks
(block_d columns); W (tiny) is resident per grid step; accumulation in f32.
The wrapper flattens any parameter pytree into a (m, D) panel, pads D to the
block size, and unflattens after mixing.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _mix_kernel(w_ref, t_ref, o_ref):
    w = w_ref[...].astype(jnp.float32)  # (m, m)
    t = t_ref[...].astype(jnp.float32)  # (m, block_d)
    o_ref[...] = jnp.dot(w, t, preferred_element_type=jnp.float32,
                         precision=jax.lax.Precision.HIGHEST).astype(
        o_ref.dtype)


def gossip_mix_panel(W, theta, *, block_d: int = 512,
                     interpret: bool | None = None):
    """W: (n, m); theta: (m, D) -> W @ theta, D tiled into VMEM blocks.

    n == m for a plain mixing matrix; the consensus-folded path passes
    n == m + 1 (W augmented with a 1^T/m row, see panel.mix_dense_mean)
    and reads the column mean off the extra output row."""
    n, m = W.shape
    D = theta.shape[1]
    block_d = min(block_d, D)
    pad = (-D) % block_d
    if pad:
        theta = jnp.pad(theta, ((0, 0), (0, pad)))
    Dp = D + pad
    nd = Dp // block_d
    out = pl.pallas_call(
        _mix_kernel,
        grid=(nd,),
        in_specs=[
            pl.BlockSpec((n, m), lambda i: (0, 0)),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
        ],
        out_specs=pl.BlockSpec((n, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((n, Dp), theta.dtype),
        interpret=interpret_mode(interpret),
    )(W, theta)
    return out[:, :D]
