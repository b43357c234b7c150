"""Fused int8 AdamW panel kernel: decode -> update -> re-encode in VMEM.

The unfused residency engine round-trips every stored moment panel
through HBM-resident f32 views each local step: decode (int8 read + f32
write), optimizer update (f32 read + f32 write), encode (f32 read + int8
write). For an (m, D) panel that is 16·m·D bytes of transient f32
traffic on top of the ~2·m·D bytes the stored int8 rep itself moves.
This kernel performs the whole companded decode, the shared elementwise
AdamW core (optim.Optimizer.core — the exact expression the pytree path
runs), and the stochastic-rounding re-encode inside one Pallas grid
sweep: HBM sees only the stored int8 q + grouped scales (plus the grad
and param panels the update must touch anyway) — no f32 moment panel is
ever materialized.

Why the re-encode can fuse at all: the kernel runs on the grouped-scale
view (wire_quant.GroupView: one scale group per view row), so every
scale group lies entirely inside one grid block and the fresh per-group
amax/127 scales of the UPDATED moments are one lane reduce per view row
— no second sweep, unlike the per-row (group=None) layout, whose row
amax needs all of D. Hence
the fused path exists only for GROUPED int8 storages ('int8'/'int8g');
per-row 'int8r' and f32/bf16 keep the unfused decode->update->encode.

Hyperparameters lr/bc1/bc2 arrive as (m, 1) per-agent columns, not
scalars: step_count diverges across agent rows after a RESYNC re-init,
so the bias corrections do too. The grid's first axis is the agent, so
each step reads its agent's (1, 1) entry.

Randomness follows wire_quant's portable contract: the uniforms are
INPUT panels threaded from the jax PRNG key schedule (bit-identical to
the kernels/ref.py oracle, runnable in interpret mode on CPU). The
uniform inputs' HBM traffic is identical in the fused and unfused paths
(both draw the same panels), so it cancels from the traffic comparison;
a TPU-native variant would draw bits on-chip via pltpu.prng_random_bits
exactly as quantize_int8_panel_native does.
"""
from __future__ import annotations

import functools

import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode
from repro.kernels.ref import adamw_fused_int8_ref
from repro.kernels.wire_quant import GroupView


def _identity(x):
    return x


def _adamw_fused_kernel(core, fwd, inv,
                        g_ref, p_ref, qm_ref, sm_ref, qv_ref, sv_ref,
                        um_ref, uv_ref, lr_ref, bc1_ref, bc2_ref,
                        po_ref, qmo_ref, smo_ref, qvo_ref, svo_ref):
    # decode: grouped dequant (one scale per view row, broadcast along
    # lanes) + inverse companding — bitwise dequantize_int8_grouped_ref
    m = inv(qm_ref[...].astype(jnp.float32) * sm_ref[...])
    v = inv(qv_ref[...].astype(jnp.float32) * sv_ref[...])
    # the shared optimizer core; lr/bc1/bc2 are this agent's (1, 1) entry
    p, m, v = core(g_ref[...], m, v, p_ref[...],
                   lr=lr_ref[...], bc1=bc1_ref[...], bc2=bc2_ref[...])
    po_ref[...] = p

    def encode(z, u, s_out, q_out):
        # fresh grouped scales of the UPDATED moment: a view row is one
        # whole group, so this matches the ref's global
        # int8_group_scale_ref exactly (max is order-independent)
        amax = jnp.max(jnp.abs(z), axis=1, keepdims=True)
        s = jnp.where(amax > 0, amax, 1.0) / 127.0
        s_out[...] = s
        q_out[...] = jnp.clip(jnp.floor(z / s + u),
                              -127.0, 127.0).astype(jnp.int8)

    encode(fwd(m), um_ref[...], smo_ref, qmo_ref)
    encode(fwd(v), uv_ref[...], svo_ref, qvo_ref)


def _col(a, m):
    """Normalize a scalar / (m,) / (m, 1) hyperparameter to an (m, 1)
    f32 column."""
    a = jnp.asarray(a, jnp.float32)
    if a.ndim == 0:
        a = a[None]
    return jnp.broadcast_to(a.reshape(-1, 1), (m, 1))


def adamw_fused_int8_panel(g, p, qm, sm, qv, sv, um, uv, lr, bc1, bc2, *,
                           group: int = 128, core, transform_fwd=None,
                           transform_inv=None, block_d: int = 512,
                           interpret: bool | None = None):
    """Fused AdamW step on companded grouped-int8 moments.

    g, p: (m, D) f32; qm/qv: (m, D) int8; sm/sv: (m, ceil(D/group)) f32
    scales; um/uv: (m, D) uniforms in [0, 1) for the stochastic
    re-encode; lr/bc1/bc2: scalar, (m,), or (m, 1) per-agent
    hyperparameters. Returns (p_new, qm_new, sm_new, qv_new, sv_new) —
    bit-identical to kernels/ref.py:adamw_fused_int8_ref."""
    m, D = g.shape
    fwd = transform_fwd if transform_fwd is not None else _identity
    inv = transform_inv if transform_inv is not None else _identity
    view = GroupView(m, D, group, block_d)
    data, scale = view.data_spec(), view.scale_spec()
    col = pl.BlockSpec((None, 1, 1), lambda a, i: (a, 0, 0))
    p_new, qm_new, sm_new, qv_new, sv_new = pl.pallas_call(
        functools.partial(_adamw_fused_kernel, core, fwd, inv),
        grid=view.grid,
        in_specs=[data, data, data, scale, data, scale,
                  data, data, col, col, col],
        out_specs=[data, data, scale, data, scale],
        out_shape=(view.data_shape(jnp.float32),
                   view.data_shape(jnp.int8), view.scale_shape(),
                   view.data_shape(jnp.int8), view.scale_shape()),
        interpret=interpret_mode(interpret),
    )(view.data(g.astype(jnp.float32)), view.data(p), view.data(qm),
      view.scale(sm), view.data(qv), view.scale(sv), view.data(um),
      view.data(uv), *(_col(h, m)[:, :, None] for h in (lr, bc1, bc2)))
    return (view.undata(p_new), view.undata(qm_new), view.unscale(sm_new),
            view.undata(qv_new), view.unscale(sv_new))


def adamw_fused_int8(g, p, qm, sm, qv, sv, um, uv, lr, bc1, bc2, *,
                     group: int = 128, core, transform_fwd=None,
                     transform_inv=None, use_pallas: bool = True,
                     interpret: bool | None = None, block_d: int = 512):
    """Dispatch wrapper: the Pallas kernel when ``use_pallas`` (the
    replicated/interpret path), else the shardable XLA ref composition —
    SPMD specs fall back here exactly as the storage codecs do via
    ``_pallas_ok``. Both branches return identical bits."""
    if use_pallas:
        return adamw_fused_int8_panel(
            g, p, qm, sm, qv, sv, um, uv, lr, bc1, bc2, group=group,
            core=core, transform_fwd=transform_fwd,
            transform_inv=transform_inv, block_d=block_d,
            interpret=interpret)
    m = g.shape[0]
    return adamw_fused_int8_ref(
        g, p, qm, sm, qv, sv, um, uv,
        _col(lr, m), _col(bc1, m), _col(bc2, m), group=group,
        transform_fwd=transform_fwd, transform_inv=transform_inv,
        core=core)
