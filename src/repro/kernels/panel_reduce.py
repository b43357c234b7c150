"""Pallas TPU kernel for fused panel statistics: column mean + consensus.

The monitoring half of the communication layer: given the (m, D) parameter
panel, one pass over the D axis produces BOTH the merged (averaged) model
``mean_j = (1/m) sum_k theta[k, j]`` and the consensus sum of squares
``sum_{k,j} (theta[k, j] - mean_j)^2`` (Xi_t^2 * m). The per-leaf tree-map
path re-reads every parameter twice (once for the mean, once for the
deviation); this kernel reads each VMEM block once and accumulates the
scalar across sequential grid steps.

TPU adaptation: D is tiled into VMEM blocks; the scalar accumulator is a
(1, 1) output block that every grid step maps to — TPU grids execute
sequentially, so read-modify-write accumulation across steps is safe
(initialised at step 0 via ``pl.when``). Zero-padding of the last block is
harmless: padded columns have mean 0 and deviation 0.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _reduce_kernel(t_ref, mean_ref, acc_ref):
    i = pl.program_id(0)
    t = t_ref[...].astype(jnp.float32)             # (m, block_d)
    mu = jnp.mean(t, axis=0, keepdims=True)        # (1, block_d)
    mean_ref[...] = mu
    sq = jnp.sum(jnp.square(t - mu))

    @pl.when(i == 0)
    def _():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += sq


def panel_mean_consensus(theta, *, block_d: int = 512,
                         interpret: bool | None = None):
    """theta: (m, D) -> (mean (D,) f32, sq scalar f32).

    ``sq`` is the total squared deviation sum_{k,j} (theta_kj - mean_j)^2;
    the consensus distance Xi is sqrt(sq / m).
    """
    m, D = theta.shape
    block_d = min(block_d, D)
    pad = (-D) % block_d
    if pad:
        theta = jnp.pad(theta, ((0, 0), (0, pad)))
    Dp = D + pad
    nd = Dp // block_d
    mean, acc = pl.pallas_call(
        _reduce_kernel,
        grid=(nd,),
        in_specs=[pl.BlockSpec((m, block_d), lambda i: (0, i))],
        out_specs=[
            pl.BlockSpec((1, block_d), lambda i: (0, i)),
            pl.BlockSpec((1, 1), lambda i: (0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Dp), jnp.float32),
            jax.ShapeDtypeStruct((1, 1), jnp.float32),
        ],
        interpret=interpret_mode(interpret),
    )(theta)
    return mean[0, :D], acc[0, 0]
