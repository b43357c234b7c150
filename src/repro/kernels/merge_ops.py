"""Pallas TPU kernels for the panel-native merge operators (repro/merging).

The heavy per-coordinate reductions of a global merge round on an (m, D)
parameter panel:

* :func:`weighted_colmerge` — precision-weighted column merge
  ``out_j = sum_k w_kj x_kj / sum_k w_kj`` with a per-coordinate weight
  panel (inverse-variance and diagonal-Fisher merging; the weights are
  cheap XLA elementwise transforms of the stat panels, the reduction over
  agents is the bandwidth-bound pass that belongs in the kernel).
* :func:`ties_colmerge` — the TIES merge body: per-row magnitude trim of
  the deviation panel, per-column sign election over the survivors, and
  the agreeing (disjoint) mean. The per-row trim THRESHOLDS are computed
  outside (``kernels/ref.py: ties_thresh_ref`` — a row quantile needs a
  full pass over D before any block can trim, exactly like the int8
  scales in ``kernels/wire_quant.py``).

TPU adaptation mirrors kernels/panel_reduce.py: D is tiled into VMEM
blocks (``block_d`` columns), the tiny (m, 1) per-row sidecar (thresholds)
is resident per grid step, math in f32 on the VPU. Columns are
independent, so there is no cross-block accumulation. Zero-padded tail
columns are sliced off after the call (a padded weighted column divides
0/0 — the NaN never escapes the discarded slice).

Both kernels are bit-identical to the ``kernels/ref.py`` oracles
(tests/test_merge_props.py); sharded specs keep the plain-XLA oracle path
so SPMD can partition the reduction, mirroring the other panel kernels.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode


def _weighted_kernel(x_ref, w_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)            # (m, block_d)
    w = w_ref[...].astype(jnp.float32)            # (m, block_d)
    num = jnp.sum(w * x, axis=0, keepdims=True)   # (1, block_d)
    den = jnp.sum(w, axis=0, keepdims=True)
    o_ref[...] = num / den


def _ties_kernel(t_ref, th_ref, o_ref):
    t = t_ref[...].astype(jnp.float32)            # (m, block_d)
    keep = jnp.abs(t) >= th_ref[...]              # (m, 1) thresholds
    tk = jnp.where(keep, t, 0.0)
    col = jnp.sum(tk, axis=0, keepdims=True)
    s = jnp.where(col >= 0.0, 1.0, -1.0)          # elected sign (ties -> +)
    agree = (tk * s) > 0.0
    cnt = jnp.sum(agree.astype(jnp.float32), axis=0, keepdims=True)
    dev = jnp.sum(jnp.where(agree, tk, 0.0), axis=0, keepdims=True)
    o_ref[...] = jnp.where(cnt > 0.0, dev / jnp.maximum(cnt, 1.0), 0.0)


def _pad_cols(x, block_d):
    m, D = x.shape
    pad = (-D) % block_d
    return (jnp.pad(x, ((0, 0), (0, pad))) if pad else x), D + pad


def weighted_colmerge(x, w, *, block_d: int = 512,
                      interpret: bool | None = None):
    """x: (m, D) panel; w: (m, D) per-coordinate weights -> (D,) f32
    weighted column merge sum_k w_kj x_kj / sum_k w_kj.

    Callers keep the denominator positive by folding their eps into w
    (the merge operators add it to the variance/Fisher stat)."""
    m, D = x.shape
    block_d = min(block_d, D)
    xp, Dp = _pad_cols(x, block_d)
    wp, _ = _pad_cols(w, block_d)
    nd = Dp // block_d
    data_spec = pl.BlockSpec((m, block_d), lambda i: (0, i))
    out = pl.pallas_call(
        _weighted_kernel,
        grid=(nd,),
        in_specs=[data_spec, data_spec],
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Dp), jnp.float32),
        interpret=interpret_mode(interpret),
    )(xp, wp)
    return out[0, :D]


def ties_colmerge(tau, thresh, *, block_d: int = 512,
                  interpret: bool | None = None):
    """tau: (m, D) deviation panel; thresh: (m, 1) f32 per-row trim
    thresholds (kernels/ref.py: ties_thresh_ref) -> (D,) f32 sign-elected
    agreeing mean of the trimmed deviations (0 where nothing survives)."""
    m, D = tau.shape
    block_d = min(block_d, D)
    tp, Dp = _pad_cols(tau, block_d)
    nd = Dp // block_d
    out = pl.pallas_call(
        _ties_kernel,
        grid=(nd,),
        in_specs=[
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, Dp), jnp.float32),
        interpret=interpret_mode(interpret),
    )(tp, thresh)
    return out[0, :D]
