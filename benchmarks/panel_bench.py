"""Microbench: fused panel communication round vs per-leaf tree-map path.

One "round" of the communication layer = gossip mixing with a random
matching W + the consensus-distance monitor; the run finishes with the
paper's single global merging. Two engines, identical math:

* **tree** — the per-leaf reference path: one tensordot per pytree leaf,
  a Python loop over leaves for the consensus monitor, one jitted dispatch
  AND one host sync per round (how launch/train.py drove rounds before the
  panel engine).
* **panel** — the flat-panel engine: state flattened once to an (m, D)
  panel, all rounds scanned on device in ONE donated dispatch, mixing as a
  single fused matmul per round, consensus as a fused reduction, a single
  device_get for the whole segment.

``python -m benchmarks.panel_bench`` writes BENCH_panel.json with
us_per_round for both paths at two sizes.

``--sharded`` adds a third engine: the SAME fused round with the panel's D
axis sharded over 'fsdp' on the (1,2,2,2) debug training mesh
(core/panel.shard_spec) — per-shard matmuls, fsdp-local collectives — and
records its us_per_round + the per-round collective bytes of the lowered
scan next to the replicated numbers (merged into BENCH_panel.json under
"sharded"). Needs 8 host devices; when the process has fewer it re-execs
itself in a subprocess with ``--xla_force_host_platform_device_count``.

Both panel engines run the consensus monitor FOLDED into the mixing
matmul (panel.mix_dense_mean: W augmented with a 1^T/m row, the mean read
off the extra output row, consensus_from_mean finishing with one deviation
pass) — no separate full-panel mean reduce per round.

``--wire <codec>[,<codec>...]|all`` benches the quantized-wire codec
subsystem (repro/wire) on the default olmo-1b-family size: per codec it
records the codec-aware wire bytes/agent/round — both the VALUES payload
(PanelSpec.wire_payload_bytes: packed int4 nibbles = 8x fewer than f32,
top-k = 1/density x) and the payload+metadata total
(wire_total_bytes: grouped scales, packed indices) — the byte ratios vs
f32, us_per_round, and the final-single-global-merge parity vs the f32
run — merged into BENCH_panel.json under "wire". The f32 codec row is
asserted BIT-exact against the no-policy engine (the identity codec
must not perturb the pre-codec path). The topk row also records its
byte-model inputs (k, density, idx_bytes, gamma) per dtype group; its
per-round bytes model the sparse gossip rounds — the single global
merge is deliberately the full-bandwidth round (see
wire/codec.py:TopKCodec).

``--residency`` benches the storage-codec residency subsystem
(repro/residency) — quantized panel residency so HBM stops capping the
agent count. Per (wire, residency) configuration it records the EXACT
per-agent resident-HBM bytes (telemetry.metrics.resident_bytes_model,
scale sidecars included) at the default olmo-1b-family size, the max
agent count per fixed memory budget, segment runtime and the
matched-seed quality delta vs the f32 engine at the cpu-preset size —
merged into BENCH_panel.json under "residency". The f32-policy row is
asserted BIT-identical to the no-policy engine; the headline row
(int8_ef wire + int8 moments/residual storage) is asserted to fit >= 2x
more agents per budget than the same wire at f32 residency, with final
eval within WIRE_MERGE_TOL.

``--telemetry`` benches the per-agent telemetry metric panels on the FULL
segment driver (core/dsgd.make_panel_segment) at the cpu-preset size:
``telemetry=False`` vs ``telemetry=True`` us_per_round (the latter adds
the five (S, m) per-agent columns — loss, grad norm, distance-to-mean,
liveness, codec wire bytes — to the single per-segment device_get),
asserting the final panels stay BIT-identical (telemetry is pure reads)
— merged into BENCH_panel.json under "telemetry".
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import gossip, topology
from repro.core import panel as panel_mod
from repro.core.consensus import consensus_distance_tree

SIZES = {
    # ~7.2M params/agent (x16 agents = 461MB state): the donation win —
    # the undonated tree path copies the full stacked state every round
    "default": dict(m=16, d_model=256, layers=8, vocab=512, rounds=8),
    # the CPU-preset training tree (what launch/train.py --preset cpu
    # runs). At this tiny scale both paths are dominated by the shared
    # memory-bound consensus reduction, so the win is smaller.
    "cpu_preset": dict(m=8, d_model=128, layers=2, vocab=256, rounds=32),
}


def _make_tree(m, d_model, layers, vocab, seed=0):
    """Agent-stacked params of a real reduced LM (olmo-1b family) — the
    honest leaf composition (embeddings, per-layer stacks, norms)."""
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("olmo-1b").reduced(d_model=d_model, layers=layers,
                                        vocab=vocab)
    model = build_model(cfg)
    return jax.vmap(model.init_params)(
        jax.random.split(jax.random.PRNGKey(seed), m))


def bench_size(m, d_model, layers, vocab, rounds, reps=3):
    tree = _make_tree(m, d_model, layers, vocab)
    spec = panel_mod.make_spec(tree)
    Ws = jnp.asarray(np.stack([
        topology.random_matching(m, 0.5, np.random.default_rng(t))
        for t in range(rounds)]), jnp.float32)

    # ---- per-leaf tree-map path: dispatch + host sync per round
    @jax.jit
    def tree_round(t, W):
        mixed = gossip.mix_dense_tree(t, W)
        return mixed, consensus_distance_tree(mixed)

    def run_tree():
        t = tree
        xi = 0.0
        for r in range(rounds):
            t, x = tree_round(t, Ws[r])
            xi = float(x)  # per-round monitor readback (old driver)
        merged = gossip.global_merge_tree(t)
        jax.block_until_ready(jax.tree.leaves(merged)[0])
        return xi

    # ---- fused panel path: one donated, scanned dispatch per segment;
    # consensus mean folded into the mixing matmul (no separate reduce)
    def seg(pan, Ws):
        def body(p, W):
            mixed, mean, _ = panel_mod.mix_dense_mean(p, W)
            return mixed, panel_mod.consensus_from_mean(mixed, mean)
        pan, xis = jax.lax.scan(body, pan, Ws)
        return panel_mod.global_merge(pan), xis

    seg_fn = jax.jit(seg, donate_argnums=(0,))

    def run_panel(pan):
        merged, xis = seg_fn(pan, Ws)
        xis = jax.device_get(xis)  # ONE transfer for the segment
        jax.block_until_ready(list(merged.values()))
        return float(xis[-1])

    def fresh_panel():
        pan = {k: v + 0.0 for k, v in  # copy: seg_fn donates its input
               panel_mod.to_panel(tree, spec).items()}
        jax.block_until_ready(list(pan.values()))
        return pan

    # numerical parity of the two engines on the same W sequence
    xi_tree = run_tree()
    xi_panel = run_panel(fresh_panel())
    assert abs(xi_tree - xi_panel) <= 1e-4 * max(abs(xi_tree), 1.0), (
        xi_tree, xi_panel)

    t_tree = []
    for _ in range(reps):
        t0 = time.perf_counter()
        run_tree()
        t_tree.append(time.perf_counter() - t0)
    t_panel = []
    for _ in range(reps):
        pan = fresh_panel()
        t0 = time.perf_counter()
        run_panel(pan)
        t_panel.append(time.perf_counter() - t0)

    us_tree = min(t_tree) / rounds * 1e6
    us_panel = min(t_panel) / rounds * 1e6
    return {"m": m, "leaves": len(jax.tree.leaves(tree)),
            "D": spec.width, "rounds": rounds,
            "us_per_round_tree": round(us_tree, 1),
            "us_per_round_panel": round(us_panel, 1),
            "speedup": round(us_tree / us_panel, 2),
            "xi_parity_gap": round(abs(xi_tree - xi_panel), 6)}


# debug training mesh used by --sharded: (pod=1, agent=2, fsdp=2, model=2)
SHARDED_DEVICES = 8


def bench_sharded(m=16, d_model=256, layers=8, vocab=512, rounds=8, reps=3):
    """Fused panel round with D sharded over 'fsdp' on the debug training
    mesh vs the replicated fused round on the same host. Returns the record
    merged into BENCH_panel.json["sharded"]."""
    from repro.launch import mesh as mesh_mod
    from repro.utils.hlo import collective_bytes

    mesh = mesh_mod.make_debug_mesh(agents=2, fsdp=2, model=2)
    tree = _make_tree(m, d_model, layers, vocab)
    repl_spec = panel_mod.make_spec(tree)
    spec = panel_mod.shard_spec(repl_spec, mesh)
    Ws = jnp.asarray(np.stack([
        topology.random_matching(m, 0.5, np.random.default_rng(t))
        for t in range(rounds)]), jnp.float32)

    def make_seg(use_spec):
        def seg(pan, Ws):
            def body(p, W):
                mixed, mean, _ = panel_mod.mix_dense_mean(p, W,
                                                          spec=use_spec)
                return mixed, panel_mod.consensus_from_mean(mixed, mean)
            pan, xis = jax.lax.scan(body, pan, Ws)
            return panel_mod.global_merge(pan, spec=use_spec), xis
        return jax.jit(seg, donate_argnums=(0,))

    def run(fn, pan):
        merged, xis = fn(pan, Ws)
        xis = jax.device_get(xis)
        jax.block_until_ready(list(merged.values()))
        return float(xis[-1])

    def fresh(use_spec):
        pan = {k: v + 0.0
               for k, v in panel_mod.to_panel(tree, repl_spec).items()}
        if use_spec is not None and use_spec.sharded:
            pan = panel_mod.shard_panel(pan, use_spec)
        jax.block_until_ready(list(pan.values()))
        return pan

    seg_repl, seg_shard = make_seg(None), make_seg(spec)
    xi_repl = run(seg_repl, fresh(None))
    xi_shard = run(seg_shard, fresh(spec))
    assert abs(xi_repl - xi_shard) <= 1e-4 * max(abs(xi_repl), 1.0), (
        xi_repl, xi_shard)

    def clock(fn, use_spec):
        ts = []
        for _ in range(reps):
            pan = fresh(use_spec)
            t0 = time.perf_counter()
            run(fn, pan)
            ts.append(time.perf_counter() - t0)
        return min(ts) / rounds * 1e6

    us_repl = clock(seg_repl, None)
    us_shard = clock(seg_shard, spec)
    txt = seg_shard.lower(fresh(spec), Ws).compile().as_text()
    per_kind, coll_total, _ = collective_bytes(txt)
    return {"backend": jax.default_backend(), "mesh": dict(mesh.shape),
            "devices": SHARDED_DEVICES, "m": m,
            "D": spec.width, "rounds": rounds,
            "pspecs": {k: str(ps) for k, ps in spec.pspecs},
            "us_per_round_replicated": round(us_repl, 1),
            "us_per_round_sharded": round(us_shard, 1),
            "coll_bytes_per_round": int(coll_total // rounds),
            "coll_kinds": sorted(per_kind),
            "xi_parity_gap": round(abs(xi_repl - xi_shard), 6)}


WIRE_CODECS = ("f32", "bf16", "int8", "int8_ef", "int4", "int4_ef",
               "topk")

# documented tolerance for the quantized final-merge parity on the
# olmo-1b reduced config: int8 error per element is <= one per-row scale
# (amax/127), int4 one GROUP scale (amax_128cols/7), and both gossip
# mixing and the global merge are convex combinations of rows, so the
# merged-model deviation stays O(scale); the EF variants carry the
# residual into the final exchange and land tighter. topk lands tightest
# of all: its damped delta mix preserves the column mean exactly and its
# global merge is the full-bandwidth round, so the merged model deviates
# from f32 only by accumulated f32 rounding.
WIRE_MERGE_TOL = 0.05


def bench_wire(codecs, m=16, d_model=256, layers=8, vocab=512, rounds=8,
               reps=3):
    """Fused panel segment per wire codec on the default olmo-1b-family
    size: codec-aware payload bytes + runtime + final-merge parity vs the
    f32 identity codec. Returns the records keyed by codec name (merged
    into BENCH_panel.json["wire"])."""
    from repro import wire as wire_mod

    tree = _make_tree(m, d_model, layers, vocab)
    base_spec = panel_mod.make_spec(tree)
    Ws = jnp.asarray(np.stack([
        topology.random_matching(m, 0.5, np.random.default_rng(t))
        for t in range(rounds)]), jnp.float32)
    wire_key = jax.random.PRNGKey(7)

    def make_seg(spec, codec):
        ef = codec is not None and codec.error_feedback

        def seg(pan, err, Ws, key):
            def body(carry, xs):
                p, e = carry
                W, k = xs
                kw = dict(spec=spec, key=k)
                if ef:
                    mixed, mean, e = panel_mod.mix_dense_mean(
                        p, W, err=e, **kw)
                else:
                    mixed, mean, _ = panel_mod.mix_dense_mean(p, W, **kw)
                return (mixed, e), panel_mod.consensus_from_mean(mixed,
                                                                 mean)
            keys = jax.random.split(key, Ws.shape[0])
            (pan, err), xis = jax.lax.scan(body, (pan, err), (Ws, keys))
            merge_key = jax.random.fold_in(key, Ws.shape[0])
            if ef:  # final exchange transmits Q(x + e): residual included
                merged, _ = panel_mod.global_merge(pan, spec=spec,
                                                   key=merge_key, err=err)
                return merged, xis
            return panel_mod.global_merge(pan, spec=spec,
                                          key=merge_key), xis
        return jax.jit(seg, donate_argnums=(0, 1))

    def fresh(codec):
        pan = {k: v + 0.0
               for k, v in panel_mod.to_panel(tree, base_spec).items()}
        # codec-seeded EF state (zeros for residuals, a panel copy for
        # the topk mirror — matches dsgd.init_panel_state)
        err = ({k: codec.init_err(v) for k, v in pan.items()}
               if codec is not None and codec.error_feedback else None)
        jax.block_until_ready(list(pan.values()))
        return pan, err

    def run(fn, codec):
        pan, err = fresh(codec)
        t0 = time.perf_counter()
        merged, xis = fn(pan, err, Ws, wire_key)
        jax.device_get(xis)
        jax.block_until_ready(list(merged.values()))
        return merged, time.perf_counter() - t0

    def clock(fn, codec):
        ts, merged = [], None
        for _ in range(reps):
            merged, dt = run(fn, codec)
            ts.append(dt)
        return merged, min(ts) / rounds * 1e6

    # no-policy engine: the pre-codec bit-exactness reference for f32
    merged_plain, _ = run(make_seg(base_spec, None), None)

    out = {}
    f32 = None
    for name in ("f32",) + tuple(c for c in codecs if c != "f32"):
        codec = wire_mod.get_codec(name)
        spec = panel_mod.with_wire(base_spec, name)
        merged, us = clock(make_seg(spec, codec), codec)
        if name == "f32":
            f32 = {"merged": merged, "us": us,
                   "payload": spec.wire_payload_bytes,
                   "total": spec.wire_total_bytes}
            gap = max(float(jnp.max(jnp.abs(a - merged_plain[k])))
                      for k, a in merged.items())
            assert gap == 0.0, (
                f"f32 identity codec perturbed the engine (max err {gap})")
        merge_err = max(
            float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - f32["merged"][k].astype(jnp.float32))))
            for k, a in merged.items())
        assert merge_err <= WIRE_MERGE_TOL, (name, merge_err)
        out[name] = {
            # per-agent bytes of one full-panel exchange: the quantized
            # VALUES payload and the payload+metadata total (grouped
            # int4 scales, packed top-k indices). For topk the per-round
            # numbers model the k-sparse gossip rounds; its single
            # global merge is the full-bandwidth round by design.
            "wire_bytes_per_agent": spec.wire_total_bytes,
            "payload_bytes_per_agent": spec.wire_payload_bytes,
            "bytes_ratio_vs_f32": round(
                f32["total"] / spec.wire_total_bytes, 2),
            "payload_ratio_vs_f32": round(
                f32["payload"] / spec.wire_payload_bytes, 2),
            "us_per_round": round(us, 1),
            "speedup_vs_f32": round(f32["us"] / us, 2),
            "merge_max_err_vs_f32": round(merge_err, 6),
            "merge_tol": WIRE_MERGE_TOL,
        }
        if hasattr(codec, "k_of"):  # record the top-k byte model inputs
            out[name]["topk_model"] = {
                "density": codec.density, "gamma": codec.gamma,
                "groups": {g: {"k": codec.k_of(w),
                               "idx_bytes": codec.idx_bytes(w)}
                           for g, w in base_spec.groups}}
    return {"backend": jax.default_backend(), "m": m, "D": base_spec.width,
            "rounds": rounds, "codecs": out}


# fixed HBM budget of the residency accounting: how many agents fit
RESIDENCY_BUDGET_GB = 8.0


def bench_residency(m=8, d_model=128, layers=2, vocab=256, rounds=8,
                    local_steps=2, batch=4, seq=32, reps=3):
    """Storage-codec residency (repro.residency) on the full segment
    driver. Two measurements per (wire, residency) row:

    * resident-HBM accounting at the DEFAULT olmo-1b-family bench size —
      the exact per-agent bytes model (params + moments + EF residual +
      merge stats, scale sidecars included) and the max agent count
      inside a fixed ``RESIDENCY_BUDGET_GB`` budget. The spec comes from
      ``jax.eval_shape``, so no default-size state is materialized.
    * matched-seed training quality + runtime at the cpu-preset size —
      same seeds, same batches, same W sequence; the uniform merged row
      and final loss are compared against the f32 engine.

    Asserts: the f32 policy is BIT-identical to the no-policy engine
    (state and metrics), every row's final loss is within
    ``WIRE_MERGE_TOL`` of f32, and the headline configuration (int8_ef
    wire + int8 moments/residual storage) fits >= 2x more agents per
    budget than the same wire at f32 residency."""
    from repro.configs import get_config
    from repro.core import dsgd
    from repro.data.synthetic import SyntheticLM, make_agent_lm_batches
    from repro.models import build_model
    from repro.optim import make_optimizer
    from repro.telemetry.metrics import (moment_traffic_model,
                                         resident_bytes_model)

    ROWS = (("f32", "f32", None),
            ("moments_bf16", "f32", "moments=bf16"),
            ("moments_int8", "f32", "moments=int8"),
            ("moments_int8g", "f32", "moments=int8g"),
            ("int8_ef_f32", "int8_ef", None),
            ("int8_ef_int8res", "int8_ef", "moments=int8,wire_err=int8"))

    # ---- analytic resident-bytes table at the default bench size
    big = SIZES["default"]
    big_tree = jax.eval_shape(
        lambda: _make_tree(big["m"], big["d_model"], big["layers"],
                           big["vocab"]))
    opt = make_optimizer("adamw", 1e-2)
    budget = int(RESIDENCY_BUDGET_GB * (1 << 30))
    table = {}
    big_width = None
    for name, wire, pol in ROWS:
        spec = panel_mod.make_spec(big_tree)
        big_width = spec.width
        if wire != "f32":
            spec = panel_mod.with_wire(spec, wire)
        spec = panel_mod.with_residency(spec, pol)
        rb = resident_bytes_model(spec, opt)
        tr = moment_traffic_model(spec, opt, local_steps=local_steps)
        # agents-per-budget off PEAK bytes (stored + the unfused path's
        # transient f32 decode views, zero under the fused kernel) —
        # the stored-only sizing the pre-fusion table used overstated
        # capacity for every unfused non-f32 policy
        table[name] = dict(rb,
                           max_agents_at_budget=budget // rb["peak"],
                           max_agents_stored_only=budget // rb["total"],
                           moment_traffic_bytes_per_round=tr[
                               "bytes_per_round"])
    ef_ratio = (table["int8_ef_f32"]["total"]
                / table["int8_ef_int8res"]["total"])
    assert ef_ratio >= 2.0, (
        "headline residency config (int8_ef wire + int8 moments/residual)"
        f" must fit >= 2x more agents per budget, got {ef_ratio:.4f}x")
    mom_ratio = table["f32"]["total"] / table["moments_int8"]["total"]

    # ---- matched-seed quality + runtime at the cpu-preset-ish size
    cfg = get_config("olmo-1b").reduced(d_model=d_model, layers=layers,
                                        vocab=vocab)
    model = build_model(cfg)
    lm = SyntheticLM(vocab=cfg.vocab_size, num_domains=4, seed=0)
    mixtures = lm.domain_mixtures(m, 0.5, seed=1)
    rng_np = np.random.default_rng(2)
    per_round = []
    for _ in range(rounds):
        hs = [make_agent_lm_batches(lm, mixtures, batch, seq, rng_np)
              for _ in range(local_steps)]
        per_round.append({k: np.stack([h[k] for h in hs]) for k in hs[0]})
    batches = {k: jnp.asarray(np.stack([r[k] for r in per_round]))
               for k in per_round[0]}
    Ws = jnp.asarray(np.stack([
        topology.random_matching(m, 0.5, np.random.default_rng(t))
        for t in range(rounds)]), jnp.float32)
    key = jax.random.PRNGKey(3)

    def fresh(wire, pol):
        state, spec = dsgd.init_panel_state(
            model.init_params, opt, m, jax.random.PRNGKey(0), wire=wire,
            residency=pol)
        jax.block_until_ready(jax.tree.leaves(state))
        return state, spec

    def clock(wire, pol):
        state, spec = fresh(wire, pol)
        seg_fn = dsgd.make_panel_segment(model.loss_fn, opt, local_steps,
                                         spec)
        final = mets = None
        ts = []
        for rep in range(reps + 1):  # rep 0 = compile
            t0 = time.perf_counter()
            final, mets = seg_fn(state, batches, Ws, key)
            mets = jax.device_get(mets)
            jax.block_until_ready(jax.tree.leaves(final))
            ts.append(time.perf_counter() - t0)
            if rep < reps:
                state, _ = fresh(wire, pol)
        row = panel_mod.merged(final["panel"], spec=spec)
        return min(ts[1:]) / rounds * 1e6, final, mets, row

    us0, fin0, mets0, row0 = clock("f32", None)
    # the f32 POLICY must compile the exact pre-residency engine
    _, fin_id, mets_id, _ = clock("f32",
                                  "moments=f32,stats=f32,wire_err=f32")
    for a, b in zip(jax.tree.leaves(fin0), jax.tree.leaves(fin_id)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            "f32 residency policy perturbed the engine state")
    for k in mets0:
        assert np.array_equal(np.asarray(mets0[k]),
                              np.asarray(mets_id[k])), k

    base_loss = float(mets0["loss"][-1])
    rows = {}
    for name, wire, pol in ROWS:
        if name == "f32":
            us, mets, row = us0, mets0, row0
        else:
            us, _, mets, row = clock(wire, pol)
        merge_err = max(float(jnp.max(jnp.abs(row[g] - row0[g])))
                        for g in row)
        loss_delta = abs(float(mets["loss"][-1]) - base_loss)
        assert loss_delta <= WIRE_MERGE_TOL, (name, loss_delta)
        rows[name] = dict(table[name], wire=wire, residency=pol or "f32",
                          us_per_round=round(us, 1),
                          final_loss=round(float(mets["loss"][-1]), 5),
                          loss_delta_vs_f32=round(loss_delta, 5),
                          merge_max_err_vs_f32=round(merge_err, 6),
                          quality_tol=WIRE_MERGE_TOL)
    return {"backend": jax.default_backend(),
            "model_size": {"m": big["m"], "D": big_width},
            "bench_size": {"m": m, "rounds": rounds,
                           "local_steps": local_steps},
            "budget_bytes": budget,
            "agents_ratio_moments_int8": round(mom_ratio, 4),
            "agents_ratio_int8_ef_int8res": round(ef_ratio, 4),
            "f32_policy_bit_identical": True,
            "rows": rows}


def bench_residency_fused(m=8, d_model=128, layers=2, vocab=256, rounds=8,
                          local_steps=2, batch=4, seq=32, reps=3):
    """The fused int8 moment kernel (kernels/opt_fused.py) vs the PR-9
    unfused decode->update->encode path, on the same harness as
    bench_residency (same seeds, same batches, same W sequence).

    * analytic per-round moment HBM traffic at the default bench size
      (metrics.moment_traffic_model): the unfused path's 16 B/scalar of
      transient f32 view traffic per stored panel vs the fused kernel's
      stored-rep-only reads/writes. Asserts the ~4x (>= 3x) reduction.
    * matched-seed training: fused and unfused int8 runs must produce
      BIT-identical final state (the fused ref path is the unfused
      composition by construction), and the fused run's final loss must
      sit within WIRE_MERGE_TOL of the f32 engine.
    * fallback byte-identity: an f32-policy engine and a bf16-moments
      engine are bit-unchanged by the fused dispatch (auto-off — the
      PR-9 paths compile verbatim).
    * measured bytes accessed per segment from XLA cost_analysis on the
      compiled fused/unfused segments — informational on CPU (interpret
      -mode Pallas inflates the fused number; the analytic model is the
      HBM-traffic headline, cf. the dryrun cost model).
    """
    from repro.configs import get_config
    from repro.core import dsgd
    from repro.data.synthetic import SyntheticLM, make_agent_lm_batches
    from repro.models import build_model
    from repro.optim import make_optimizer
    from repro.telemetry.metrics import (fused_moments_auto,
                                         moment_traffic_model,
                                         resident_bytes_model)

    # ---- analytic moment-traffic model at the default bench size
    big = SIZES["default"]
    big_tree = jax.eval_shape(
        lambda: _make_tree(big["m"], big["d_model"], big["layers"],
                           big["vocab"]))
    opt = make_optimizer("adamw", 1e-2)
    spec_big = panel_mod.with_residency(panel_mod.make_spec(big_tree),
                                        "moments=int8")
    assert fused_moments_auto(spec_big, opt), \
        "int8 moments + adamw must auto-qualify for the fused kernel"
    tr_fused = moment_traffic_model(spec_big, opt, local_steps=local_steps,
                                    fused=True)
    tr_unfused = moment_traffic_model(spec_big, opt,
                                      local_steps=local_steps, fused=False)
    traffic_ratio = (tr_unfused["bytes_per_round"]
                     / tr_fused["bytes_per_round"])
    assert traffic_ratio >= 3.0, (
        "fused int8 moment update must cut per-round moment HBM traffic "
        f">= 3x vs the unfused path, model says {traffic_ratio:.2f}x")
    rb_fused = resident_bytes_model(spec_big, opt, fused=True)
    rb_unfused = resident_bytes_model(spec_big, opt, fused=False)

    # ---- matched-seed fused vs unfused vs f32 at the cpu-preset size
    cfg = get_config("olmo-1b").reduced(d_model=d_model, layers=layers,
                                        vocab=vocab)
    model = build_model(cfg)
    lm = SyntheticLM(vocab=cfg.vocab_size, num_domains=4, seed=0)
    mixtures = lm.domain_mixtures(m, 0.5, seed=1)
    rng_np = np.random.default_rng(2)
    per_round = []
    for _ in range(rounds):
        hs = [make_agent_lm_batches(lm, mixtures, batch, seq, rng_np)
              for _ in range(local_steps)]
        per_round.append({k: np.stack([h[k] for h in hs]) for k in hs[0]})
    batches = {k: jnp.asarray(np.stack([r[k] for r in per_round]))
               for k in per_round[0]}
    Ws = jnp.asarray(np.stack([
        topology.random_matching(m, 0.5, np.random.default_rng(t))
        for t in range(rounds)]), jnp.float32)
    key = jax.random.PRNGKey(3)

    def fresh(pol):
        state, spec = dsgd.init_panel_state(
            model.init_params, opt, m, jax.random.PRNGKey(0),
            residency=pol)
        jax.block_until_ready(jax.tree.leaves(state))
        return state, spec

    def clock(pol, fused):
        state, spec = fresh(pol)
        seg_fn = dsgd.make_panel_segment(model.loss_fn, opt, local_steps,
                                         spec, fused=fused)
        compiled = seg_fn.lower(state, batches, Ws, key).compile()
        ca = compiled.cost_analysis() or {}
        if isinstance(ca, list):
            ca = ca[0] if ca else {}
        bytes_acc = float(ca.get("bytes accessed", 0.0))
        final = mets = None
        ts = []
        for rep in range(reps + 1):  # rep 0 = compile
            t0 = time.perf_counter()
            final, mets = seg_fn(state, batches, Ws, key)
            mets = jax.device_get(mets)
            jax.block_until_ready(jax.tree.leaves(final))
            ts.append(time.perf_counter() - t0)
            if rep < reps:
                state, _ = fresh(pol)
        return min(ts[1:]) / rounds * 1e6, final, mets, bytes_acc

    us_f32, fin_f32, mets_f32, _ = clock(None, None)
    us_fused, fin_fused, mets_fused, ba_fused = clock("moments=int8", True)
    us_unf, fin_unf, mets_unf, ba_unf = clock("moments=int8", False)

    # fused vs unfused: same SR keys, same core expression -> same bits
    for a, b in zip(jax.tree.leaves(fin_fused), jax.tree.leaves(fin_unf)):
        assert np.array_equal(np.asarray(a), np.asarray(b)), (
            "fused int8 moment update diverged from the unfused path")
    loss_delta = abs(float(mets_fused["loss"][-1])
                     - float(mets_f32["loss"][-1]))
    assert loss_delta <= WIRE_MERGE_TOL, (
        f"fused int8 final loss drifted {loss_delta} from f32")

    # fallback byte-identity: policies outside the fused capability
    # (f32 identity, bf16 moments) must compile the PR-9 engine verbatim
    # whether the dispatch default (auto) or an explicit off is used
    for pol in (None, "moments=bf16"):
        _, fin_a, mets_a, _ = clock(pol, None)
        _, fin_b, mets_b, _ = clock(pol, False)
        for a, b in zip(jax.tree.leaves(fin_a), jax.tree.leaves(fin_b)):
            assert np.array_equal(np.asarray(a), np.asarray(b)), (
                f"fused auto-dispatch perturbed the fallback path {pol}")
        for k in mets_a:
            assert np.array_equal(np.asarray(mets_a[k]),
                                  np.asarray(mets_b[k])), (pol, k)

    return {"backend": jax.default_backend(),
            "model_size": {"m": big["m"], "D": spec_big.width},
            "bench_size": {"m": m, "rounds": rounds,
                           "local_steps": local_steps},
            "moment_traffic_bytes_per_round": {
                "fused": tr_fused["bytes_per_round"],
                "unfused": tr_unfused["bytes_per_round"]},
            "moment_traffic_ratio": round(traffic_ratio, 4),
            "resident_peak_bytes": {"fused": rb_fused["peak"],
                                    "unfused": rb_unfused["peak"]},
            "transient_bytes": {"fused": rb_fused["transient_bytes"],
                                "unfused": rb_unfused["transient_bytes"]},
            "us_per_round": {"f32": round(us_f32, 1),
                             "int8_fused": round(us_fused, 1),
                             "int8_unfused": round(us_unf, 1)},
            "measured_bytes_accessed_per_segment": {
                "int8_fused": ba_fused, "int8_unfused": ba_unf},
            "final_loss": {
                "f32": round(float(mets_f32["loss"][-1]), 5),
                "int8_fused": round(float(mets_fused["loss"][-1]), 5),
                "int8_unfused": round(float(mets_unf["loss"][-1]), 5)},
            "loss_delta_vs_f32": round(loss_delta, 5),
            "quality_tol": WIRE_MERGE_TOL,
            "fused_vs_unfused_bit_identical": True,
            "fallback_bit_identical": True}


def bench_telemetry(m=8, d_model=128, layers=2, vocab=256, rounds=8,
                    local_steps=2, batch=4, seq=32, reps=3):
    """Per-agent telemetry overhead on the full segment driver
    (dsgd.make_panel_segment): the same donated scanned segment with
    ``telemetry=False`` vs ``telemetry=True`` (which adds the five (S, m)
    metric panels — per-agent loss / grad norm / distance-to-mean /
    liveness trit / codec wire bytes — to the single per-segment
    device_get). Asserts the no-perturbation invariant (final panels
    BIT-identical) and records both runtimes + the extra metric payload
    bytes per round. Merged into BENCH_panel.json["telemetry"]."""
    from repro.configs import get_config
    from repro.core import dsgd
    from repro.data.synthetic import SyntheticLM, make_agent_lm_batches
    from repro.models import build_model
    from repro.optim import make_optimizer

    cfg = get_config("olmo-1b").reduced(d_model=d_model, layers=layers,
                                        vocab=vocab)
    model = build_model(cfg)
    opt = make_optimizer("adamw", 1e-2)

    lm = SyntheticLM(vocab=cfg.vocab_size, num_domains=4, seed=0)
    mixtures = lm.domain_mixtures(m, 0.5, seed=1)
    rng_np = np.random.default_rng(2)
    per_round = []
    for _ in range(rounds):
        hs = [make_agent_lm_batches(lm, mixtures, batch, seq, rng_np)
              for _ in range(local_steps)]
        per_round.append({k: np.stack([h[k] for h in hs]) for k in hs[0]})
    batches = {k: jnp.asarray(np.stack([r[k] for r in per_round]))
               for k in per_round[0]}
    Ws = jnp.asarray(np.stack([
        topology.random_matching(m, 0.5, np.random.default_rng(t))
        for t in range(rounds)]), jnp.float32)
    key = jax.random.PRNGKey(3)

    def fresh():  # segment donates its state: rebuild per rep (same key)
        state, spec = dsgd.init_panel_state(model.init_params, opt, m,
                                            jax.random.PRNGKey(0))
        jax.block_until_ready(jax.tree.leaves(state))
        return state, spec

    def run(seg_fn, state):
        state, mets = seg_fn(state, batches, Ws, key)
        mets = jax.device_get(mets)  # the segment's ONE transfer
        jax.block_until_ready(jax.tree.leaves(state))
        return state, mets

    def clock(telemetry):
        state, spec = fresh()
        seg_fn = dsgd.make_panel_segment(model.loss_fn, opt, local_steps,
                                         spec, telemetry=telemetry)
        state, mets = run(seg_fn, state)  # compile
        final = state
        ts = []
        for _ in range(reps):
            state, _ = fresh()
            t0 = time.perf_counter()
            final, mets = run(seg_fn, state)
            ts.append(time.perf_counter() - t0)
        return min(ts) / rounds * 1e6, final, mets

    us_off, pan_off, _ = clock(False)
    us_on, pan_on, mets = clock(True)
    for k, a in pan_off["panel"].items():  # no-perturbation invariant
        assert np.array_equal(np.asarray(a),
                              np.asarray(pan_on["panel"][k])), k
    # the five per-agent columns: 3x f32 + 2x int32 per agent per round
    extra = sorted(k for k in mets
                   if k in ("loss_agent", "grad_norm_agent", "dist_to_mean",
                            "live", "wire_bytes"))
    return {"backend": jax.default_backend(), "m": m, "rounds": rounds,
            "local_steps": local_steps,
            "us_per_round_off": round(us_off, 1),
            "us_per_round_on": round(us_on, 1),
            "overhead_pct": round((us_on / us_off - 1.0) * 100, 1),
            "agent_metrics": extra,
            "extra_bytes_per_round": int(m * (3 * 4 + 2 * 4)),
            "panels_bit_identical": True}


def bench_checkpoint(m=16, d_model=256, layers=8, vocab=512, reps=3):
    """Checkpoint subsystem on the default-size panel train state
    (int8_ef residuals + fisher stats panels included): blob size,
    blocking save / restore wall time, and the ASYNC handoff time — how
    long Checkpointer.save(block=False) holds the caller (the host
    snapshot) before the training loop may continue into the next
    donated segment. Merged into BENCH_panel.json["checkpoint"]."""
    import shutil
    import tempfile

    from repro.checkpoint import Checkpointer, restore
    from repro.configs import get_config
    from repro.core import dsgd
    from repro.models import build_model
    from repro.optim import make_optimizer

    cfg = get_config("olmo-1b").reduced(d_model=d_model, layers=layers,
                                        vocab=vocab)
    model = build_model(cfg)
    opt = make_optimizer("adamw", 1e-2)
    state, spec = dsgd.init_panel_state(model.init_params, opt, m,
                                        jax.random.PRNGKey(0),
                                        wire="int8_ef", merger="fisher")
    jax.block_until_ready(jax.tree.leaves(state))
    tmp = tempfile.mkdtemp(prefix="ckpt_bench_")
    try:
        ck = Checkpointer(tmp, keep=2)
        save_s, restore_s, handoff_s = [], [], []
        for step in range(reps):
            t0 = time.perf_counter()
            ck.save(step, state, block=True)
            save_s.append(time.perf_counter() - t0)
        path = os.path.join(tmp, f"step_{reps - 1:08d}.ckpt")
        nbytes = os.path.getsize(path)
        for _ in range(reps):
            t0 = time.perf_counter()
            restore(path, state)
            restore_s.append(time.perf_counter() - t0)
        for step in range(reps):
            t0 = time.perf_counter()
            ck.save(100 + step, state, block=False)
            handoff_s.append(time.perf_counter() - t0)
            ck.wait()
        return {"m": m, "D": spec.width, "bytes": nbytes,
                "save_s": round(min(save_s), 4),
                "restore_s": round(min(restore_s), 4),
                "async_handoff_s": round(min(handoff_s), 4)}
    finally:
        shutil.rmtree(tmp, ignore_errors=True)


def _load_existing():
    if os.path.exists("BENCH_panel.json"):
        with open("BENCH_panel.json") as f:
            return json.load(f)
    return {}


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--sharded", action="store_true",
                    help="bench the fsdp-sharded panel on the debug mesh "
                         "(re-execs with forced host devices if needed)")
    ap.add_argument("--wire",
                    help="bench wire codecs (repro.wire) against the f32 "
                         "identity: codec-aware bytes/agent/round "
                         "(payload + total) + runtime + final-merge "
                         "parity. A codec name, a comma-separated list "
                         "('int8,int4,topk'), or 'all'")
    ap.add_argument("--telemetry", action="store_true",
                    help="bench the per-agent telemetry metric panels on "
                         "the full segment driver: telemetry off vs on "
                         "us_per_round, overhead pct, and the bit-"
                         "identical-panels invariant")
    ap.add_argument("--residency", action="store_true",
                    help="bench the storage-codec residency subsystem "
                         "(repro.residency): exact resident bytes/agent "
                         "per policy, max agents per memory budget, and "
                         "matched-seed quality vs the f32 engine "
                         "(f32 policy asserted bit-identical; int8_ef + "
                         "int8 moments/residual asserted >= 2x agents)")
    ap.add_argument("--checkpoint", action="store_true",
                    help="bench the checkpoint subsystem on the default-"
                         "size train state: blob bytes, save/restore wall "
                         "time, async-save handoff time")
    args = ap.parse_args()
    if args.wire and args.wire != "all":
        unknown = [c for c in args.wire.split(",") if c not in WIRE_CODECS]
        if unknown:
            ap.error(f"unknown wire codecs {unknown}; "
                     f"known: {list(WIRE_CODECS)} or 'all'")

    # decided from the environment, before anything touches a backend: a
    # parent holding a device would keep it from the re-executed child
    force = f"--xla_force_host_platform_device_count={SHARDED_DEVICES}"
    if args.sharded and force not in os.environ.get("XLA_FLAGS", ""):
        env = dict(os.environ)
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " " + force).strip()
        env.setdefault("JAX_PLATFORMS", "cpu")
        argv = [sys.executable, "-m", "benchmarks.panel_bench", "--sharded"]
        if args.wire:  # keep a combined --sharded --wire request intact
            argv += ["--wire", args.wire]
        raise SystemExit(subprocess.run(argv, env=env).returncode)

    out = _load_existing()
    out.setdefault("description",
                   "fused panel gossip+merge round vs per-leaf tree-map "
                   "path (us_per_round)")

    if args.wire:
        names = (WIRE_CODECS if args.wire == "all"
                 else tuple(args.wire.split(",")))
        rec = bench_wire(names, **SIZES["default"])
        wire = out.setdefault("wire", {})
        wire.update({k: v for k, v in rec.items() if k != "codecs"})
        wire.setdefault("codecs", {}).update(rec["codecs"])
        for name, r in rec["codecs"].items():
            print(f"wire {name}: {r['payload_bytes_per_agent']}B payload "
                  f"/{r['wire_bytes_per_agent']}B total per agent "
                  f"({r['payload_ratio_vs_f32']}x/"
                  f"{r['bytes_ratio_vs_f32']}x vs f32) "
                  f"{r['us_per_round']:.0f}us/round "
                  f"merge_err={r['merge_max_err_vs_f32']}", flush=True)
    if args.sharded:
        out["sharded"] = bench_sharded(**{k: v for k, v in
                                          SIZES["default"].items()})
        r = out["sharded"]
        print(f"sharded: replicated={r['us_per_round_replicated']:.0f}us "
              f"fsdp-sharded={r['us_per_round_sharded']:.0f}us "
              f"coll={r['coll_bytes_per_round']}B/round", flush=True)
    if args.telemetry:
        out["telemetry"] = bench_telemetry()
        r = out["telemetry"]
        print(f"telemetry: off={r['us_per_round_off']:.0f}us "
              f"on={r['us_per_round_on']:.0f}us "
              f"overhead={r['overhead_pct']}% "
              f"(+{r['extra_bytes_per_round']}B/round host readback)",
              flush=True)
    if args.residency:
        out["residency"] = bench_residency()
        r = out["residency"]
        hl = r["rows"]["int8_ef_int8res"]
        print(f"residency: int8_ef + int8 moments/residual = "
              f"{hl['total']}B/agent resident vs "
              f"{r['rows']['int8_ef_f32']['total']}B at f32 "
              f"({r['agents_ratio_int8_ef_int8res']}x agents per "
              f"{r['budget_bytes'] >> 30}GiB: "
              f"{hl['max_agents_at_budget']} vs "
              f"{r['rows']['int8_ef_f32']['max_agents_at_budget']}), "
              f"loss_delta={hl['loss_delta_vs_f32']}", flush=True)
        out["residency_fused"] = bench_residency_fused()
        rf = out["residency_fused"]
        tb = rf["moment_traffic_bytes_per_round"]
        print(f"residency_fused: moment traffic "
              f"{tb['unfused']}B -> {tb['fused']}B per round "
              f"({rf['moment_traffic_ratio']}x less), "
              f"fused==unfused bits: "
              f"{rf['fused_vs_unfused_bit_identical']}, "
              f"loss_delta_vs_f32={rf['loss_delta_vs_f32']}", flush=True)
    if args.checkpoint:
        out["checkpoint"] = bench_checkpoint(
            **{k: v for k, v in SIZES["default"].items() if k != "rounds"})
        r = out["checkpoint"]
        print(f"checkpoint: {r['bytes'] / 1e6:.1f}MB "
              f"save={r['save_s'] * 1e3:.0f}ms "
              f"restore={r['restore_s'] * 1e3:.0f}ms "
              f"async_handoff={r['async_handoff_s'] * 1e3:.0f}ms",
              flush=True)
    if (not args.wire and not args.sharded and not args.checkpoint
            and not args.telemetry and not args.residency):
        # default: the sizes sweep
        out["backend"] = jax.default_backend()  # labels the "sizes" runs
        out.setdefault("sizes", {})
        for name, kw in SIZES.items():
            out["sizes"][name] = bench_size(**kw)
            r = out["sizes"][name]
            print(f"{name}: tree={r['us_per_round_tree']:.0f}us "
                  f"panel={r['us_per_round_panel']:.0f}us "
                  f"speedup={r['speedup']}x", flush=True)
    with open("BENCH_panel.json", "w") as f:
        json.dump(out, f, indent=1)
    print("wrote BENCH_panel.json")


if __name__ == "__main__":
    main()
