"""Pure-jnp oracles for the Pallas kernels (the allclose ground truth)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def attention_ref(q, k, v, *, causal: bool = True, window=None,
                  scale=None):
    """q,k,v: (B, S, H, hd) (same H; GQA is expanded by the wrapper).

    Returns (B, S, H, hd). Masking: causal and/or sliding window."""
    B, S, H, hd = q.shape
    scale = scale if scale is not None else 1.0 / np.sqrt(hd)
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    qp = jnp.arange(S)[:, None]
    kp = jnp.arange(S)[None, :]
    ok = jnp.ones((S, S), bool)
    if causal:
        ok &= kp <= qp
    if window is not None:
        ok &= kp > qp - window
    scores = jnp.where(ok[None, None], scores, -1e30)
    attn = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", attn, v)


def gossip_mix_ref(W, theta):
    """W: (m, m); theta: (m, D) -> W @ theta in f32 accumulation."""
    return jnp.matmul(W.astype(jnp.float32), theta.astype(jnp.float32),
                      precision="highest").astype(theta.dtype)


def panel_mean_consensus_ref(theta):
    """theta: (m, D) -> (column mean (D,) f32, total squared deviation).

    Oracle for kernels/panel_reduce.py: mean_j = (1/m) sum_k theta_kj and
    sq = sum_{k,j} (theta_kj - mean_j)^2 (= m * Xi^2)."""
    t = theta.astype(jnp.float32)
    mean = jnp.mean(t, axis=0)
    sq = jnp.sum(jnp.square(t - mean[None]))
    return mean, sq


def int8_scale_ref(x):
    """Per-row symmetric int8 scale for an (m, D) panel: amax_k / 127 in
    f32, with all-zero rows mapped to scale 1/127 so dequantization is
    always a plain multiply."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=1, keepdims=True)
    return jnp.where(amax > 0, amax, 1.0) / 127.0


def quantize_int8_ref(x, scale, u=None):
    """x: (m, D); scale: (m, 1) f32 -> int8 values in [-127, 127].

    Oracle for kernels/wire_quant.py. ``u`` (same shape as x, uniform in
    [0, 1)) selects stochastic rounding floor(x/scale + u) — unbiased in
    expectation over u; ``u=None`` rounds to nearest (ties to even,
    matching jnp.round). The clip guards the float boundary rows where
    x/scale lands an ulp outside +/-127."""
    s = x.astype(jnp.float32) / scale
    q = jnp.floor(s + u) if u is not None else jnp.round(s)
    return jnp.clip(q, -127.0, 127.0).astype(jnp.int8)


def dequantize_int8_ref(q, scale):
    """q: (m, D) int8; scale: (m, 1) f32 -> f32 panel q * scale."""
    return q.astype(jnp.float32) * scale


def int4_group_scale_ref(x, group: int = 128):
    """Grouped symmetric int4 scales for an (m, D) panel: one amax/7 scale
    per row per ``group``-column block -> (m, ceil(D/group)) f32. A
    partial tail group reduces over its real columns only; all-zero
    groups map to scale 1/7 (dequantization stays a plain multiply)."""
    m, D = x.shape
    gn = -(-D // group)
    pad = gn * group - D
    mag = jnp.abs(x.astype(jnp.float32))
    if pad:
        mag = jnp.pad(mag, ((0, 0), (0, pad)))
    amax = jnp.max(mag.reshape(m, gn, group), axis=2)
    return jnp.where(amax > 0, amax, 1.0) / 7.0


def expand_group_scale(scale, D: int, group: int = 128):
    """(m, ceil(D/group)) grouped scales -> (m, D): each scale repeated
    over its column group (tail group truncated to the real width)."""
    return jnp.repeat(scale, group, axis=1)[:, :D]


def quantize_int4_ref(x, scale, u=None, group: int = 128):
    """x: (m, D); scale: (m, ceil(D/group)) f32 -> int8 values in [-7, 7]
    (the int4 staging dtype before nibble packing).

    Oracle for kernels/wire_quant.py:quantize_int4_panel. ``u`` (same
    shape as x, uniform [0, 1)) selects stochastic rounding
    floor(x/scale + u); ``u=None`` rounds to nearest."""
    s = x.astype(jnp.float32) / expand_group_scale(scale, x.shape[1], group)
    q = jnp.floor(s + u) if u is not None else jnp.round(s)
    return jnp.clip(q, -7.0, 7.0).astype(jnp.int8)


def dequantize_int4_ref(q, scale, group: int = 128):
    """q: (m, D) int4-valued int8; scale: (m, ceil(D/group)) f32 -> f32."""
    return (q.astype(jnp.float32)
            * expand_group_scale(scale, q.shape[1], group))


def int8_group_scale_ref(x, group: int = 128):
    """Grouped symmetric int8 scales for an (m, D) panel: one amax/127
    scale per row per ``group``-column block -> (m, ceil(D/group)) f32
    (the int4 grouped-scale layout at int8 range — the 'int8g' storage
    codec). Partial tail groups reduce over their real columns only;
    all-zero groups map to scale 1/127."""
    m, D = x.shape
    gn = -(-D // group)
    pad = gn * group - D
    mag = jnp.abs(x.astype(jnp.float32))
    if pad:
        mag = jnp.pad(mag, ((0, 0), (0, pad)))
    amax = jnp.max(mag.reshape(m, gn, group), axis=2)
    return jnp.where(amax > 0, amax, 1.0) / 127.0


def quantize_int8_grouped_ref(x, scale, u=None, group: int = 128):
    """x: (m, D); scale: (m, ceil(D/group)) f32 -> int8 in [-127, 127].

    Oracle for kernels/wire_quant.py:quantize_int8_grouped_panel (the
    'int8g' residency storage). Same rounding contract as
    quantize_int8_ref: ``u`` selects stochastic floor(x/scale + u),
    ``u=None`` rounds to nearest."""
    s = x.astype(jnp.float32) / expand_group_scale(scale, x.shape[1], group)
    q = jnp.floor(s + u) if u is not None else jnp.round(s)
    return jnp.clip(q, -127.0, 127.0).astype(jnp.int8)


def dequantize_int8_grouped_ref(q, scale, group: int = 128):
    """q: (m, D) int8; scale: (m, ceil(D/group)) f32 -> f32 panel."""
    return (q.astype(jnp.float32)
            * expand_group_scale(scale, q.shape[1], group))


def pack_int4_ref(q):
    """(m, D) int4-valued int8 -> (m, ceil(D/2)) uint8 packed nibbles:
    byte j holds column j in its LOW nibble and column j + ceil(D/2) in
    its HIGH nibble (an odd D packs the last byte against a zero nibble).
    This IS the wire byte layout — two quantized values per byte, halves
    that a TPU kernel reads as two lane-aligned blocks."""
    m, D = q.shape
    P = (D + 1) // 2
    lo = q[:, :P].astype(jnp.uint8) & 0xF
    hi = jnp.pad(q[:, P:], ((0, 0), (0, 2 * P - D))).astype(jnp.uint8) & 0xF
    return (lo | (hi << 4)).astype(jnp.uint8)


def unpack_int4_ref(p, D: int):
    """(m, ceil(D/2)) uint8 packed nibbles -> (m, D) int8, sign-extended
    ((n ^ 8) - 8 maps the nibble back to [-8, 7]). Exact inverse of
    pack_int4_ref for values in [-8, 7]."""
    lo = (p & 0xF).astype(jnp.int8)
    hi = ((p >> 4) & 0xF).astype(jnp.int8)
    q = jnp.concatenate([lo, hi], axis=1)[:, :D]
    return ((q ^ 8) - 8).astype(jnp.int8)


def topk_threshold_ref(x, k: int):
    """Per-row magnitude threshold of the top-k sparsifier: the k-th
    largest |x| per row. x: (m, D) -> (m, 1) f32. Computed OUTSIDE the
    sparsify kernel (a full row pass, like the int8 scales)."""
    mag = jnp.abs(x.astype(jnp.float32))
    vals = jax.lax.top_k(mag, k)[0]
    return vals[:, -1:]


def sparsify_topk_ref(x, thresh):
    """Zero every entry whose magnitude is below its row threshold.
    x: (m, D); thresh: (m, 1) f32 -> f32 panel.

    Oracle for kernels/wire_quant.py:sparsify_topk_panel. Ties AT the
    threshold all survive (measure-zero for continuous inputs; the wire
    payload accounting assumes exactly k survivors per row)."""
    x32 = x.astype(jnp.float32)
    return jnp.where(jnp.abs(x32) >= thresh, x32, 0.0)


def weighted_colmerge_ref(x, w):
    """x: (m, D) panel; w: (m, D) per-coordinate nonneg weights ->
    (D,) f32 weighted column merge sum_k w_kj x_kj / sum_k w_kj.

    Oracle for kernels/merge_ops.py:weighted_colmerge (the variance- and
    Fisher-weighted merge operators). Callers keep the denominator
    positive by folding their eps into w BEFORE the merge."""
    x32 = x.astype(jnp.float32)
    w32 = w.astype(jnp.float32)
    return jnp.sum(w32 * x32, axis=0) / jnp.sum(w32, axis=0)


def ties_thresh_ref(tau, trim):
    """Per-agent-row magnitude threshold of the TIES trim step: keep the
    top ``trim`` fraction of |tau| per row (trim=1.0 keeps everything).
    tau: (m, D) deviations -> (m, 1) f32 thresholds (row quantiles).
    Computed OUTSIDE the merge kernel (a full row pass, like the int8
    scales in wire_quant)."""
    if not 0.0 < trim <= 1.0:
        raise ValueError(f"trim fraction must be in (0, 1], got {trim}")
    mag = jnp.abs(tau.astype(jnp.float32))
    return jnp.quantile(mag, 1.0 - trim, axis=1, keepdims=True)


def ties_colmerge_ref(tau, thresh):
    """TIES column merge of trimmed deviations (sign election + agreeing
    mean). tau: (m, D) deviations from the reference row; thresh: (m, 1)
    per-row magnitude thresholds (ties_thresh_ref) -> (D,) f32.

    Per column j: trim entries below their row threshold, elect the sign
    of the trimmed column sum (ties -> +), and average ONLY the surviving
    entries that agree with the elected sign (the disjoint mean of TIES);
    columns with no survivor merge to 0 (pure reference).

    Oracle for kernels/merge_ops.py:ties_colmerge."""
    t = tau.astype(jnp.float32)
    keep = jnp.abs(t) >= thresh
    tk = jnp.where(keep, t, 0.0)
    col = jnp.sum(tk, axis=0)
    s = jnp.where(col >= 0.0, 1.0, -1.0)
    agree = (tk * s[None]) > 0.0
    cnt = jnp.sum(agree.astype(jnp.float32), axis=0)
    dev = jnp.sum(jnp.where(agree, tk, 0.0), axis=0)
    return jnp.where(cnt > 0.0, dev / jnp.maximum(cnt, 1.0), 0.0)


def adamw_fused_int8_ref(g, p, qm, sm, qv, sv, um, uv, lr, bc1, bc2, *,
                         group: int = 128, transform_fwd=None,
                         transform_inv=None, core=None):
    """Oracle for kernels/opt_fused.py: fused int8 Adam moment update.

    Decodes the companded int8 moments (dequant -> inverse transform),
    runs the shared elementwise optimizer ``core`` (optim.Optimizer.core
    — the exact expression the pytree path executes), then re-encodes
    the new moments (forward transform -> fresh grouped scales ->
    stochastic floor with the supplied uniforms). By construction this
    is the unfused decode->update->encode composition on the ref path,
    so fused-off and fused-on-ref trajectories are bit-identical.

    g, p: (m, D) f32 grads/params; qm, qv: (m, D) int8; sm, sv:
    (m, ceil(D/group)) f32 scales; um, uv: (m, D) uniforms in [0, 1);
    lr, bc1, bc2: broadcastable to (m, D) — (m, 1) columns carry the
    per-agent step_count divergence after RESYNC.
    Returns (p_new, qm_new, sm_new, qv_new, sv_new).
    """
    fwd = transform_fwd if transform_fwd is not None else (lambda x: x)
    inv = transform_inv if transform_inv is not None else (lambda z: z)
    m_dec = inv(dequantize_int8_grouped_ref(qm, sm, group=group))
    v_dec = inv(dequantize_int8_grouped_ref(qv, sv, group=group))
    p_new, m_new, v_new = core(g, m_dec, v_dec, p, lr=lr, bc1=bc1, bc2=bc2)
    zm = fwd(m_new)
    zv = fwd(v_new)
    sm_new = int8_group_scale_ref(zm, group=group)
    qm_new = quantize_int8_grouped_ref(zm, sm_new, um, group=group)
    sv_new = int8_group_scale_ref(zv, group=group)
    qv_new = quantize_int8_grouped_ref(zv, sv_new, uv, group=group)
    return p_new, qm_new, sm_new, qv_new, sv_new
