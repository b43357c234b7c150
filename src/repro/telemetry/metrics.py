"""Per-agent metric panels: on-device (m,) observables for the segment
scan.

The segment driver (``dsgd.make_panel_segment(telemetry=True)``) stacks
these per-round vectors into (S, m) metric arrays — per-agent loss, grad
norm, distance-to-mean (the consensus decomposition), liveness trit and
wire bytes — returned alongside the scalar metrics in the SAME single
``device_get`` per segment. Everything here is a pure read of panels the
round already materialized: telemetry must never perturb the trajectory
(pinned by tests/test_telemetry.py).

Wire-byte accounting reuses the exact codec cost model
(:attr:`PanelSpec.wire_total_bytes` — payload + scales/indices): a row
of W equal to the identity row communicates nothing and pays 0; a delta
(mirror) codec's GLOBAL round is full bandwidth by design
(``panel.global_merge``), so it pays the storage bytes; a RESYNC agent
pays the full-precision pull. Bytes are int32 — exact up to 2 GiB per
agent-round, which covers every panel this repo ships (a 1B-param f32
panel is ~4 GB and would need the dryrun byte model instead).
"""
from __future__ import annotations

from typing import Optional

import jax.numpy as jnp


def agent_loss(losses, alive=None):
    """(m,) per-agent loss; non-live rows report 0 (they took no step)."""
    if alive is None:
        return losses.astype(jnp.float32)
    return jnp.where(alive, losses.astype(jnp.float32), 0.0)


def agent_grad_norm(gpan, alive=None):
    """(m,) per-agent gradient l2 norm across all dtype groups of a grad
    panel; non-live rows report 0."""
    total = None
    for x in gpan.values():
        x32 = x.astype(jnp.float32)
        sq = jnp.sum(x32 * x32, axis=tuple(range(1, x32.ndim)))
        total = sq if total is None else total + sq
    gn = jnp.sqrt(total)
    if alive is None:
        return gn
    return jnp.where(alive, gn, 0.0)


def agent_dist_to_mean(panel, live=None):
    """(m,) per-agent distance to the panel mean — the consensus
    decomposition: ``consensus_distance`` is exactly
    ``sqrt(mean(dist**2))`` of these rows (live-weighted under a
    liveness mask). Dead/stale rows still report their distance to the
    LIVE mean: how far a stale agent has drifted is precisely the
    straggler signal the per-agent panel exists for."""
    first = next(iter(panel.values()))
    m = first.shape[0]
    if live is None:
        w = jnp.full((m,), 1.0 / m, jnp.float32)
    else:
        lf = live.astype(jnp.float32)
        w = lf / jnp.maximum(jnp.sum(lf), 1.0)
    total = jnp.zeros((m,), jnp.float32)
    for x in panel.values():
        x32 = x.astype(jnp.float32)
        mean = jnp.tensordot(w, x32, axes=1, precision="highest")
        total = total + jnp.sum(jnp.square(x32 - mean[None]), axis=1)
    return jnp.sqrt(total)


def wire_bytes_model(spec, wire_dtype=None):
    """Host-side (bytes_wire, bytes_full) per agent per full-panel
    exchange: the codec-aware wire cost (``spec.wire_total_bytes``, or
    the legacy cast's itemsize model) and the full-precision storage
    cost (what a delta codec's global round or a RESYNC pull moves)."""
    bytes_full = sum(jnp.dtype(k).itemsize * w for k, w in spec.groups)
    if wire_dtype is not None:
        it = jnp.dtype(wire_dtype).itemsize
        return sum(it * w for _, w in spec.groups), bytes_full
    return spec.wire_total_bytes, bytes_full


def fused_moments_auto(spec, optimizer) -> bool:
    """Whether the fused in-VMEM moment update (kernels/opt_fused.py)
    applies to this spec+optimizer — the single eligibility predicate
    the segment driver, the accounting models and the launcher all
    consult. True iff the moments policy storage advertises
    ``fused_update`` (grouped int8), the optimizer exposes the shared
    elementwise ``core``/``hyper`` with the (m, v) moment layout the
    kernel hardcodes, and the spec has an f32 group for the policy to
    act on."""
    from repro import residency as residency_mod
    if optimizer is None or optimizer.core is None or optimizer.hyper is None:
        return False
    if tuple(optimizer.moment_keys) != ("m", "v"):
        return False
    st = residency_mod.get_storage(spec.residency_of("moments"))
    if not (getattr(st, "fused_update", False) and st.needs_key):
        return False
    return any(g == "float32" for g, _ in spec.groups)


def resident_bytes_model(spec, optimizer=None, wire_dtype=None, fused=None):
    """Host-side exact per-agent resident HBM bytes of the engine's
    panel state under the spec's residency policy — the storage-codec
    counterpart of :func:`wire_bytes_model`.

    Returns ``{"params", "moments", "wire_err", "merge_stat", "total",
    "transient_bytes", "peak"}`` in bytes per agent, scale sidecars
    included (:meth:`PanelSpec.storage_bytes`). Moments count
    ``optimizer.moment_keys`` panels (AdamW's two when ``optimizer`` is
    None) and mirror each group's native dtype, so only f32 groups pay
    the storage codec; the wire-error residual exists only when the wire
    policy runs error feedback (and the legacy ``wire_dtype`` cast,
    which disables EF, zeroes it); merge statistics count the spec
    merger's ``stat_panels``. This model is pinned exact against
    ``jax.eval_shape`` of the real state by the residency conformance
    tests.

    ``total`` is the STORED footprint that persists across the whole
    segment. ``transient_bytes`` is the in-round peak of the f32 decode
    views the unfused path materializes for non-f32 stored panels
    (moments each local step, stats at round entry, the EF residual
    inside the communicating branches) — the term the pre-fusion
    accounting silently dropped, understating peak HBM. The moments
    term is zero when the fused kernel is active (``fused=None`` infers
    :func:`fused_moments_auto`; pass the launcher's resolved flag to
    pin it). ``peak = total + transient_bytes`` is what capacity
    planning (agents-per-HBM-budget) must use for the unfused engine."""
    from repro import merging as merging_mod
    from repro import residency as residency_mod
    from repro import wire as wire_mod
    params = sum(jnp.dtype(k).itemsize * w for k, w in spec.groups)
    n_mom = 2 if optimizer is None else len(optimizer.moment_keys)
    moments = n_mom * spec.storage_bytes("moments")
    needs_ef = wire_dtype is None and any(
        wire_mod.get_codec(spec.wire_of(k)).error_feedback
        for k, _ in spec.groups)
    wire_err = (spec.storage_bytes("wire_err", state_dtype="float32")
                if needs_ef else 0)
    merger = merging_mod.get_merger(spec.merger)
    merge_stat = (len(merger.stat_panels)
                  * spec.storage_bytes("stats", state_dtype="float32"))
    out = {"params": params, "moments": moments, "wire_err": wire_err,
           "merge_stat": merge_stat}
    out["total"] = sum(out.values())
    if fused is None:
        fused = fused_moments_auto(spec, optimizer)
    f32_w = sum(w for g, w in spec.groups if g == "float32")
    all_w = sum(w for _, w in spec.groups)
    transient = 0
    if not fused and residency_mod.get_storage(
            spec.residency_of("moments")).name != "f32":
        transient += n_mom * 4 * f32_w
    if needs_ef and residency_mod.get_storage(
            spec.residency_of("wire_err")).name != "f32":
        transient += 4 * all_w
    if merger.stat_panels and residency_mod.get_storage(
            spec.residency_of("stats")).name != "f32":
        transient += len(merger.stat_panels) * 4 * all_w
    out["transient_bytes"] = transient
    out["peak"] = out["total"] + transient
    return out


def moment_traffic_model(spec, optimizer=None, local_steps: int = 1,
                         fused=None):
    """Host-side per-agent HBM bytes MOVED per round by the optimizer
    moment panels — the bandwidth counterpart of
    :func:`resident_bytes_model` (which counts bytes held).

    Every local step, each moment panel pays a stored-rep read + write
    (both paths). The unfused path additionally round-trips a
    materialized f32 view per stored panel: decode write + update
    read + update write + encode read = 16 bytes/scalar of transient
    traffic on top of the ~2 bytes/scalar the int8 rep itself moves —
    the gap the fused kernel closes. Uniform SR-input traffic is
    identical in both paths (both draw the same (m, D) panels from the
    same keys) so it cancels from the comparison; the TPU-native
    variant draws its bits on-chip (wire_quant.quantize_int8_panel_
    native) and pays it in neither.

    Returns ``{"stored_bytes_per_step", "transient_bytes_per_step",
    "bytes_per_step", "bytes_per_round"}``."""
    from repro import residency as residency_mod
    n_mom = 2 if optimizer is None else len(optimizer.moment_keys)
    st = residency_mod.get_storage(spec.residency_of("moments"))
    if fused is None:
        fused = fused_moments_auto(spec, optimizer)
    stored = transient = 0
    for g, w in spec.groups:
        if g == "float32":
            stored += 2 * st.resident_bytes(1, w)
            if st.name != "f32" and not fused:
                transient += 16 * w
        else:
            stored += 2 * jnp.dtype(g).itemsize * w
    per_step = n_mom * (stored + transient)
    return {"stored_bytes_per_step": n_mom * stored,
            "transient_bytes_per_step": n_mom * transient,
            "bytes_per_step": per_step,
            "bytes_per_round": per_step * local_steps}


def round_wire_bytes(W, *, bytes_wire: int, bytes_full: int,
                     full_bandwidth=None, lv=None):
    """(m,) int32 wire bytes each agent paid this round.

    Identity rows of W (idle agents, unmatched partners, the degraded
    rows of dead agents) pay 0 — nothing travels their wire, mirroring
    the engine's per-row idle rule. ``full_bandwidth`` (traced bool; a
    delta codec's global round) switches communicating rows to the
    full-precision cost; ``lv`` (the (m,) liveness trit) zeroes DEAD
    rows and charges RESYNC rows the full-precision pull."""
    m = W.shape[0]
    idle = jnp.all(W == jnp.eye(m, dtype=W.dtype), axis=1)
    per = jnp.where(idle, 0, bytes_wire)
    if full_bandwidth is not None:
        per = jnp.where(jnp.logical_and(full_bandwidth, ~idle),
                        bytes_full, per)
    if lv is not None:
        per = jnp.where(lv == 0, 0, per)
        per = jnp.where(lv == 2, bytes_full, per)
    return per.astype(jnp.int32)


def live_trits(lv, m: int):
    """(m,) int32 liveness column for the metric panel (all-LIVE when the
    round carries no mask)."""
    if lv is None:
        return jnp.ones((m,), jnp.int32)
    return lv.astype(jnp.int32)
