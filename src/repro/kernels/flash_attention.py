"""Flash attention Pallas TPU kernel (causal, optional sliding window).

TPU adaptation: 2D grid (q-block, k-block) with the k dimension iterated
sequentially ("arbitrary" dimension semantics) so the online-softmax running
max / denominator / accumulator live in VMEM scratch across k steps.
BlockSpecs tile Q/K/V into (block, head_dim) VMEM windows; MXU-aligned
block sizes (multiples of 128) are chosen by the wrapper in ops.py.

Validated in interpret mode against kernels/ref.py on CPU; on a TPU the
same pallas_call lowers to Mosaic.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import interpret_mode

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
                  block_q: int, block_k: int, causal: bool, window,
                  scale: float, num_k_blocks: int):
    qi = pl.program_id(0)
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[...].astype(jnp.float32)  # (block_q, hd)
    k = k_ref[...].astype(jnp.float32)  # (block_k, hd)
    v = v_ref[...].astype(jnp.float32)

    s = jnp.dot(q, k.T, preferred_element_type=jnp.float32) * scale

    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, (block_q, block_k), 1)
    ok = jnp.ones_like(q_pos, dtype=bool)
    if causal:
        ok &= k_pos <= q_pos
    if window is not None:
        ok &= k_pos > q_pos - window
    s = jnp.where(ok, s, NEG_INF)

    m_prev = m_scr[...]  # (block_q, 1)
    m_cur = jnp.max(s, axis=1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_new = alpha * l_scr[...] + jnp.sum(p, axis=1, keepdims=True)
    acc_scr[...] = acc_scr[...] * alpha + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_scr[...] = m_new
    l_scr[...] = l_new

    @pl.when(ki == num_k_blocks - 1)
    def _flush():
        o_ref[...] = (acc_scr[...] /
                      jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def flash_attention_bh(q, k, v, *, causal=True, window=None, scale=None,
                       block_q=128, block_k=128, interpret=None):
    """Single (batch*head)-merged call. q,k,v: (BH, S, hd)."""
    BH, S, hd = q.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0
    nq, nk = S // block_q, S // block_k

    kernel = functools.partial(
        _flash_kernel, block_q=block_q, block_k=block_k, causal=causal,
        window=window, scale=scale, num_k_blocks=nk)

    def one(qi, ki_, vi):
        return pl.pallas_call(
            kernel,
            grid=(nq, nk),
            in_specs=[
                pl.BlockSpec((block_q, hd), lambda i, j: (i, 0)),
                pl.BlockSpec((block_k, hd), lambda i, j: (j, 0)),
                pl.BlockSpec((block_k, hd), lambda i, j: (j, 0)),
            ],
            out_specs=pl.BlockSpec((block_q, hd), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((S, hd), qi.dtype),
            scratch_shapes=[
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, 1), jnp.float32),
                pltpu.VMEM((block_q, hd), jnp.float32),
            ],
            interpret=interpret_mode(interpret),
        )(qi, ki_, vi)

    return jax.vmap(one)(q, k, v)
