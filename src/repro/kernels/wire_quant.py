"""Pallas TPU kernels for the quantized-wire codecs: int8/int4 panel
(de)quant, int4 nibble (un)packing, and the top-k sparsifier.

The wire codecs' hot ops on an (m, D) parameter panel: quantize each
agent's row to int8 against a per-row symmetric scale (optionally with
stochastic rounding), to int4 against GROUPED per-row/per-``group``-column
scales with the values packed two-per-byte on the wire, sparsify a row to
its top-k-magnitude entries against a per-row threshold, and dequantize
back to f32 on the receive side. TPU adaptation mirrors
kernels/gossip_mix.py: D is tiled into VMEM blocks (``block_d`` columns),
the tiny per-row scale/threshold columns are resident per grid step, math
in f32 on the VPU.

Grouped scales (int4, grouped int8): the wrappers view the (m, D) panel
as (m, G, group) — one scale group per row of the view — so a grid step
holds a (rows, group) data block and its (rows, 1) scale column. Every
block then satisfies the TPU tiling rule (last dim the full ``group``,
second-to-last a multiple of 8), and the kernels are the per-row ones:
a scale broadcasts along lanes, and no in-kernel reshape is needed.

Randomness: stochastic rounding is floor(x/scale + u) with u uniform in
[0, 1). The portable entry point takes ``u`` as an INPUT panel (threaded
from a jax PRNG key by the codec layer — bit-identical to the
``kernels/ref.py`` oracle, and runnable in interpret mode on CPU where
``pltpu.prng_seed`` has no lowering). ``quantize_int8_panel_native`` is
the TPU-only variant that draws the bits on-chip from a scalar seed
(``pltpu.prng_random_bits``), saving the (m, D) uniform input's HBM
traffic on real hardware.

Scales and top-k thresholds are computed OUTSIDE the kernels
(``kernels/ref.py``: int8_scale_ref / int4_group_scale_ref /
topk_threshold_ref — cheap XLA row-reduces): the row amax / k-th-largest
needs a full pass over D before any block can quantize, so fusing it in
would force a second grid sweep for no bandwidth win.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode
from repro.kernels.ref import (int4_group_scale_ref, int8_group_scale_ref,
                               int8_scale_ref, topk_threshold_ref)


def _round_kernel(qmax, x_ref, s_ref, o_ref):
    s = x_ref[...].astype(jnp.float32) / s_ref[...]
    o_ref[...] = jnp.clip(jnp.round(s), -qmax, qmax).astype(jnp.int8)


def _stoch_kernel(qmax, x_ref, s_ref, u_ref, o_ref):
    s = x_ref[...].astype(jnp.float32) / s_ref[...]
    o_ref[...] = jnp.clip(jnp.floor(s + u_ref[...]),
                          -qmax, qmax).astype(jnp.int8)


def _dequant_kernel(q_ref, s_ref, o_ref):
    o_ref[...] = q_ref[...].astype(jnp.float32) * s_ref[...]


def _quant_kernel(qmax, stochastic):
    return functools.partial(_stoch_kernel if stochastic else _round_kernel,
                             qmax)


def _pad_cols(x, block_d):
    m, D = x.shape
    pad = (-D) % block_d
    return (jnp.pad(x, ((0, 0), (0, pad))) if pad else x), D + pad


def quantize_int8_panel(x, scale=None, u=None, *, block_d: int = 512,
                        interpret: bool | None = None):
    """x: (m, D) float panel -> (q int8 (m, D), scale (m, 1) f32).

    ``scale`` defaults to the per-row amax/127 (int8_scale_ref). ``u``
    (uniform [0, 1), same shape as x) switches round-to-nearest to
    stochastic rounding; zero-padded tail columns quantize to 0."""
    m, D = x.shape
    if scale is None:
        scale = int8_scale_ref(x)
    block_d = min(block_d, D)
    xp, Dp = _pad_cols(x, block_d)
    nd = Dp // block_d
    scale_spec = pl.BlockSpec((m, 1), lambda i: (0, 0))
    data_spec = pl.BlockSpec((m, block_d), lambda i: (0, i))
    ops, in_specs = [xp, scale], [data_spec, scale_spec]
    if u is not None:
        ops.append(_pad_cols(u, block_d)[0])
        in_specs.append(data_spec)
    q = pl.pallas_call(
        _quant_kernel(127.0, u is not None),
        grid=(nd,),
        in_specs=in_specs,
        out_specs=data_spec,
        out_shape=jax.ShapeDtypeStruct((m, Dp), jnp.int8),
        interpret=interpret_mode(interpret),
    )(*ops)
    return q[:, :D], scale


def quantize_int8_panel_native(x, seed, scale=None, *, block_d: int = 512):
    """TPU-only stochastic quantize drawing bits on-chip from ``seed``
    (int32 scalar): no (m, D) uniform input, so the only HBM traffic is
    x in / q out. ``pltpu.prng_seed`` has no CPU/interpret lowering, so
    this path runs only on a TPU; the portable
    ``quantize_int8_panel(u=...)`` is the verified oracle-parity path."""
    from jax.experimental.pallas import tpu as pltpu

    m, D = x.shape
    if scale is None:
        scale = int8_scale_ref(x)
    block_d = min(block_d, D)
    xp, Dp = _pad_cols(x, block_d)
    nd = Dp // block_d

    def kernel(seed_ref, x_ref, s_ref, o_ref):
        # distinct stream per grid step: the block index is passed as a
        # SEPARATE seed word so pltpu.prng_seed hashes (seed, block)
        # together — seed + program_id would alias consecutive caller
        # seeds onto shifted copies of the same streams (round t block i
        # == round t+1 block i-1), correlating the rounding across
        # rounds. Low 24 bits -> f32-exact uniform.
        pltpu.prng_seed(seed_ref[0], pl.program_id(0))
        bits = pltpu.prng_random_bits(x_ref.shape)
        u = (bits & 0xFFFFFF).astype(jnp.float32) * (1.0 / (1 << 24))
        s = x_ref[...].astype(jnp.float32) / s_ref[...]
        o_ref[...] = jnp.clip(jnp.floor(s + u),
                              -127.0, 127.0).astype(jnp.int8)

    q = pl.pallas_call(
        kernel,
        grid=(nd,),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((m, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m, Dp), jnp.int8),
        interpret=False,
    )(jnp.asarray([seed], jnp.int32), xp, scale)
    return q[:, :D], scale


def dequantize_int8_panel(q, scale, *, block_d: int = 512,
                          interpret: bool | None = None):
    """q: (m, D) int8; scale: (m, 1) f32 -> f32 panel q * scale."""
    m, D = q.shape
    block_d = min(block_d, D)
    qp, Dp = _pad_cols(q, block_d)
    nd = Dp // block_d
    out = pl.pallas_call(
        _dequant_kernel,
        grid=(nd,),
        in_specs=[
            pl.BlockSpec((m, block_d), lambda i: (0, i)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((m, block_d), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m, Dp), jnp.float32),
        interpret=interpret_mode(interpret),
    )(qp, scale)
    return out[:, :D]


# ------------------------------------------------------ grouped scales


class GroupView:
    """The (m, D) -> (m, Gp, group) view of a grouped-scale panel: one
    scale group per view row, Gp padded to whole blocks of ``rows`` view
    rows (about ``block_d`` columns per grid step; a multiple of 32, the
    int8 sublane tiling, unless one block covers every group). The grid
    is (agents, row blocks)."""

    def __init__(self, m: int, D: int, group: int, block_d: int):
        self.m, self.D, self.group = m, D, group
        self.G = -(-D // group)
        rows = max(32, (block_d // group) // 32 * 32)
        self.rows = self.G if self.G <= rows else rows
        self.Gp = -(-self.G // self.rows) * self.rows
        self.grid = (m, self.Gp // self.rows)

    def data(self, x):
        """(m, D) -> (m, Gp, group), zero-padded."""
        pad = self.Gp * self.group - self.D
        if pad:
            x = jnp.pad(x, ((0, 0), (0, pad)))
        return x.reshape(self.m, self.Gp, self.group)

    def scale(self, s):
        """(m, G) scales -> (m, Gp, 1); pad groups get scale 1.0 (their
        values are zero, so any nonzero scale works)."""
        pad = self.Gp - s.shape[1]
        if pad:
            s = jnp.pad(s, ((0, 0), (0, pad)), constant_values=1.0)
        return s[:, :, None]

    def undata(self, y):
        return y.reshape(self.m, self.Gp * self.group)[:, :self.D]

    def unscale(self, s):
        return s[:, :self.G, 0]

    def data_spec(self):
        return pl.BlockSpec((None, self.rows, self.group),
                            lambda a, i: (a, i, 0))

    def scale_spec(self):
        return pl.BlockSpec((None, self.rows, 1), lambda a, i: (a, i, 0))

    def data_shape(self, dtype):
        return jax.ShapeDtypeStruct((self.m, self.Gp, self.group), dtype)

    def scale_shape(self):
        return jax.ShapeDtypeStruct((self.m, self.Gp, 1), jnp.float32)


def _quantize_grouped(x, scale, u, qmax, group, block_d, interpret):
    v = GroupView(*x.shape, group, block_d)
    ops = [v.data(x), v.scale(scale)]
    in_specs = [v.data_spec(), v.scale_spec()]
    if u is not None:
        ops.append(v.data(u))
        in_specs.append(v.data_spec())
    q = pl.pallas_call(
        _quant_kernel(qmax, u is not None),
        grid=v.grid,
        in_specs=in_specs,
        out_specs=v.data_spec(),
        out_shape=v.data_shape(jnp.int8),
        interpret=interpret_mode(interpret),
    )(*ops)
    return v.undata(q)


def _dequantize_grouped(q, scale, group, block_d, interpret):
    v = GroupView(*q.shape, group, block_d)
    out = pl.pallas_call(
        _dequant_kernel,
        grid=v.grid,
        in_specs=[v.data_spec(), v.scale_spec()],
        out_specs=v.data_spec(),
        out_shape=v.data_shape(jnp.float32),
        interpret=interpret_mode(interpret),
    )(v.data(q), v.scale(scale))
    return v.undata(out)


def quantize_int4_panel(x, scale=None, u=None, *, group: int = 128,
                        block_d: int = 512, interpret: bool | None = None):
    """x: (m, D) float panel -> (q int4-valued int8 (m, D),
    scale (m, ceil(D/group)) f32).

    ``scale`` defaults to the grouped amax/7 (int4_group_scale_ref); one
    scale per row per ``group`` columns rides each view row (GroupView).
    ``u`` (uniform [0, 1), shape of x) switches round-to-nearest to
    stochastic rounding."""
    if scale is None:
        scale = int4_group_scale_ref(x, group)
    return (_quantize_grouped(x, scale, u, 7.0, group, block_d, interpret),
            scale)


def dequantize_int4_panel(q, scale, *, group: int = 128,
                          block_d: int = 512, interpret: bool | None = None):
    """q: (m, D) int4-valued int8; scale (m, ceil(D/group)) f32 -> f32."""
    return _dequantize_grouped(q, scale, group, block_d, interpret)


# the 'int8g' residency storage layout: int8 range against the int4
# kernels' grouped-scale blocking (one scale per row per ``group``
# columns), for state panels whose row amax is dominated by a few
# coordinates


def quantize_int8_grouped_panel(x, scale=None, u=None, *, group: int = 128,
                                block_d: int = 512,
                                interpret: bool | None = None):
    """x: (m, D) float panel -> (q int8 (m, D),
    scale (m, ceil(D/group)) f32).

    ``scale`` defaults to the grouped amax/127 (int8_group_scale_ref);
    blocking as in quantize_int4_panel. ``u`` (uniform [0, 1), shape of
    x) selects stochastic rounding. Matches
    kernels/ref.py:quantize_int8_grouped_ref bit-for-bit."""
    if scale is None:
        scale = int8_group_scale_ref(x, group)
    return (_quantize_grouped(x, scale, u, 127.0, group, block_d,
                              interpret), scale)


def dequantize_int8_grouped_panel(q, scale, *, group: int = 128,
                                  block_d: int = 512,
                                  interpret: bool | None = None):
    """q: (m, D) int8; scale (m, ceil(D/group)) f32 -> f32 panel."""
    return _dequantize_grouped(q, scale, group, block_d, interpret)


# --------------------------------------------------------- int4 nibbles
# Packed layout (kernels/ref.py:pack_int4_ref): byte j of a row holds
# column j in its low nibble and column j + ceil(D/2) in its high one, so
# the kernels read and write two lane-aligned halves — no interleave.


def _pack4_kernel(lo_ref, hi_ref, o_ref):
    lo = lo_ref[...].astype(jnp.int32) & 0xF
    hi = hi_ref[...].astype(jnp.int32) & 0xF
    o_ref[...] = (lo | (hi << 4)).astype(jnp.uint8)


def _unpack4_kernel(p_ref, lo_ref, hi_ref):
    p = p_ref[...].astype(jnp.int32)
    lo_ref[...] = (((p & 0xF) ^ 8) - 8).astype(jnp.int8)
    hi_ref[...] = ((((p >> 4) & 0xF) ^ 8) - 8).astype(jnp.int8)


def _nibble_block(P: int, block_d: int) -> int:
    return max(1, min(block_d // 2, P))


def pack_int4_panel(q, *, block_d: int = 512,
                    interpret: bool | None = None):
    """(m, D) int4-valued int8 -> (m, ceil(D/2)) uint8 packed nibbles
    (first half of the columns low, second half high — the wire byte
    layout). Matches kernels/ref.py:pack_int4_ref bit-for-bit."""
    m, D = q.shape
    P = (D + 1) // 2
    bp = _nibble_block(P, block_d)
    lo, Pp = _pad_cols(q[:, :P], bp)
    hi = jnp.pad(q[:, P:], ((0, 0), (0, Pp - (D - P))))
    spec = pl.BlockSpec((m, bp), lambda i: (0, i))
    out = pl.pallas_call(
        _pack4_kernel,
        grid=(Pp // bp,),
        in_specs=[spec, spec],
        out_specs=spec,
        out_shape=jax.ShapeDtypeStruct((m, Pp), jnp.uint8),
        interpret=interpret_mode(interpret),
    )(lo, hi)
    return out[:, :P]


def unpack_int4_panel(p, D: int, *, block_d: int = 512,
                      interpret: bool | None = None):
    """(m, ceil(D/2)) uint8 packed nibbles -> (m, D) int8, sign-extended.
    Exact inverse of pack_int4_panel."""
    m, P = p.shape
    bp = _nibble_block(P, block_d)
    pp, Pp = _pad_cols(p, bp)
    spec = pl.BlockSpec((m, bp), lambda i: (0, i))
    half = jax.ShapeDtypeStruct((m, Pp), jnp.int8)
    lo, hi = pl.pallas_call(
        _unpack4_kernel,
        grid=(Pp // bp,),
        in_specs=[spec],
        out_specs=[spec, spec],
        out_shape=[half, half],
        interpret=interpret_mode(interpret),
    )(pp)
    return jnp.concatenate([lo[:, :P], hi[:, :D - P]], axis=1)


# -------------------------------------------------------------- top-k


def _sparsify_kernel(x_ref, t_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    o_ref[...] = jnp.where(jnp.abs(x) >= t_ref[...], x, 0.0)


def sparsify_topk_panel(x, thresh=None, *, k: int = None,
                        block_d: int = 512, interpret: bool | None = None):
    """Zero every entry below its per-row top-k magnitude threshold.

    ``thresh`` (m, 1) defaults to the k-th largest |x| per row
    (topk_threshold_ref — computed outside the kernel like the int8
    scales). The threshold column is resident per grid step; zero-padded
    tail columns stay zero. Matches sparsify_topk_ref bit-for-bit."""
    m, D = x.shape
    if thresh is None:
        if k is None:
            raise ValueError("sparsify_topk_panel needs thresh= or k=")
        thresh = topk_threshold_ref(x, k)
    bd = min(block_d, D)
    xp, Dp = _pad_cols(x, bd)
    nd = Dp // bd
    out = pl.pallas_call(
        _sparsify_kernel,
        grid=(nd,),
        in_specs=[
            pl.BlockSpec((m, bd), lambda i: (0, i)),
            pl.BlockSpec((m, 1), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((m, bd), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((m, Dp), jnp.float32),
        interpret=interpret_mode(interpret),
    )(xp, thresh)
    return out[:, :D]
