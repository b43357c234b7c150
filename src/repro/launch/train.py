"""Decentralized LM training launcher (flat-panel engine).

Runs the paper's algorithm end-to-end on real data (synthetic non-IID token
streams): per-agent local AdamW/SGD steps + scheduled gossip communication +
(optionally) the single final global merging. The training state lives as a
persistent (m, D) parameter panel (core/panel.py); the host loop dispatches
ONE donated, scanned computation per schedule *segment* (``--segment``
rounds) with the segment's mixing matrices precomputed and stacked, H
DISTINCT batches per round (Algorithm 1's local SGD), on-device metric
accumulation, and a single device_get per segment.

``--preset cpu`` runs a tiny model on the CPU (tests); ``--preset chip``
keeps every published width of the architecture and cuts depth only, so
m agents fit one 16 GB v5e chip (``--mesh host`` lays the agent rows over
all of a host's chips instead); on a pod the same script drives the
production training mesh: ``--mesh train``
builds mesh.make_training_mesh and shards the panel rows over
('pod','agent') and the flat D axis over 'fsdp' (core/panel.shard_spec), so
the fused mix lowers to per-shard matmuls with fsdp-local collectives
instead of silently requiring replicated state. ``--mesh debug`` runs the
same lowering on the (1,2,2,2) debug mesh (needs
``XLA_FLAGS=--xla_force_host_platform_device_count=8``).

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --preset cpu \
      --rounds 20 --schedule final_merge
  XLA_FLAGS=--xla_force_host_platform_device_count=8 PYTHONPATH=src \
      python -m repro.launch.train --preset cpu --mesh debug --rounds 4
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import signal
import time

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro import merging as merging_mod
from repro import telemetry
from repro import wire as wire_mod
from repro.checkpoint import Checkpointer, save
from repro.configs import PRESETS, get_config, preset_config
from repro.core import dsgd
from repro.core import faults as faults_mod
from repro.core import merge as merge_mod
from repro.core import panel as panel_mod
from repro.core.schedule import make_schedule
from repro.data.synthetic import SyntheticLM, make_agent_lm_batches
from repro.launch import mesh as mesh_mod
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.optim import make_optimizer
from repro.residency import parse_policy
from repro.telemetry.metrics import fused_moments_auto, resident_bytes_model


def build_mesh(kind: str, preset: str, cfg):
    """Resolve --mesh: None (single-device/replicated panels) or a
    ('pod','agent','fsdp','model') training mesh the panel is sharded on
    (``host``: the agent axis over every chip of this host)."""
    if kind == "auto":
        kind = "train" if preset == "pod" else "none"
    if kind == "none":
        return None
    if kind == "train":
        return mesh_mod.make_training_mesh(cfg.dist.agents_per_pod)
    if kind == "host":
        return mesh_mod.make_host_mesh()
    if kind == "debug":
        need = 8
        if jax.device_count() < need:
            raise SystemExit(
                f"--mesh debug needs {need} devices; set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={need}")
        return mesh_mod.make_debug_mesh(agents=2, fsdp=2, model=2)
    raise ValueError(kind)


def sample_segment_batches(lm, mixtures, rounds, local_steps, batch, seq,
                           rng_np):
    """(S, H, m, b, seq) batches: H DISTINCT batches per round, so every
    local step sees fresh data (Algorithm 1's local SGD; the old driver
    repeated one batch H times)."""
    per_round = []
    for _ in range(rounds):
        hs = [make_agent_lm_batches(lm, mixtures, batch, seq, rng_np)
              for _ in range(local_steps)]
        per_round.append({k: np.stack([h[k] for h in hs]) for k in hs[0]})
    return {k: jnp.asarray(np.stack([r[k] for r in per_round]))
            for k in per_round[0]}


def main(argv=None):
    """Run the training job ``argv`` (default: the command line) describes.
    Returns {"history": per-round records, "merged": the merged model as an
    f32 pytree} for callers that drive the job in-process."""
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--preset", default="cpu", choices=PRESETS,
                    help="cpu: tiny widths for tests; chip: published "
                         "widths, depth cut to fit one v5e chip; pod: the "
                         "published config")
    ap.add_argument("--agents", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=30)
    ap.add_argument("--local-steps", type=int, default=4)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--segment", type=int, default=8,
                    help="rounds per donated scanned dispatch (adaptive "
                         "schedule forces 1: it needs per-round feedback)")
    ap.add_argument("--schedule", default="final_merge",
                    choices=["constant", "local", "windowed", "final_merge",
                             "periodic", "adaptive"])
    ap.add_argument("--window-start", type=int, default=0)
    ap.add_argument("--window-end", type=int, default=0)
    ap.add_argument("--optimizer", default="adamw")
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--alpha", type=float, default=0.1,
                    help="Dirichlet heterogeneity")
    ap.add_argument("--wire", default="f32",
                    choices=sorted(wire_mod.CODECS),
                    help="gossip wire codec (repro.wire): bf16 halves wire "
                         "bytes, int8 cuts them ~4x (per-agent scales + "
                         "stochastic rounding), int4 ~8x (packed nibbles, "
                         "grouped scales), *_ef adds error feedback (an "
                         "extra donated residual panel), topk ships only "
                         "the k largest innovations per agent against a "
                         "mirror panel (error feedback built in)")
    ap.add_argument("--residency", default="",
                    help="storage-codec policy for the engine's state "
                         "panels (repro.residency): 'kind=codec' pairs "
                         "joined by ',' over kinds moments/stats/wire_err "
                         "and codecs f32/bf16/int8/int8g, or a bare codec "
                         "for the moments (e.g. 'moments=int8,stats=bf16'"
                         "). Params stay f32; int8 moments cut resident "
                         "HBM ~4x per moment panel (stochastic rounding, "
                         "per-row scales; int8g = grouped scales). Empty/"
                         "f32 = the bit-exact pre-residency engine")
    ap.add_argument("--fused-moments", default="auto",
                    choices=["auto", "on", "off"],
                    help="fused int8 moment update (kernels/opt_fused.py):"
                         " decode, AdamW core and stochastic re-encode in "
                         "one kernel sweep, no transient f32 moment view "
                         "in HBM (~4x less moment traffic per local "
                         "step). auto = on whenever the --residency "
                         "moments storage is grouped int8 and the "
                         "optimizer exposes a fused core; the fused path "
                         "is trajectory-identical to the unfused one, so "
                         "'off' is a debugging/measurement switch")
    ap.add_argument("--merge", default="uniform",
                    choices=sorted(merging_mod.MERGERS),
                    help="merge operator applied on global rounds "
                         "(repro.merging): uniform mean, weighted "
                         "(inverse consensus distance), var/fisher "
                         "(per-coordinate precision weighting; extra "
                         "donated stats panels), ties (sign election + "
                         "trim), swa (merge of per-agent EMA "
                         "accumulators)")
    ap.add_argument("--eval-merged-every", type=int, default=0,
                    help="counterfactual merged-model eval cadence in "
                         "rounds (core.merge.counterfactual_eval with "
                         "--merge's operator; Fig. 2c curves). 0 = once "
                         "per segment (the previous behavior). NOTE: a "
                         "nonzero cadence re-chops the scan segments, and "
                         "the per-segment rng split means runs are only "
                         "trajectory-comparable at the SAME cadence")
    ap.add_argument("--mesh", default="auto",
                    choices=["auto", "none", "train", "host", "debug"],
                    help="shard the (m, D) panel on a training mesh: rows "
                         "over ('pod','agent'), D over 'fsdp' (auto: train "
                         "for --preset pod, none otherwise; host: agent "
                         "rows over this host's chips)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", default="results/train")
    ap.add_argument("--save-merged", default="")
    ap.add_argument("--faults", default="",
                    help="deterministic fault plan 'AGENT@KILL[-REJOIN]' "
                         "joined by ';' (core.faults.FaultPlan.parse): the "
                         "agent is dead from round KILL, rejoins at round "
                         "REJOIN by pulling the live agents' merged model "
                         "(e.g. '2@5-9;0@3')")
    ap.add_argument("--checkpoint-every", type=int, default=0,
                    help="save a resumable panel checkpoint every N "
                         "SEGMENTS (0 = off); saves are asynchronous "
                         "(background commit off a host snapshot)")
    ap.add_argument("--checkpoint-dir", default="",
                    help="checkpoint directory (default: "
                         "OUT/ckpt_<run tag>)")
    ap.add_argument("--checkpoint-keep", type=int, default=3,
                    help="retain only the newest K checkpoints")
    ap.add_argument("--resume", action="store_true",
                    help="resume from the newest good checkpoint in the "
                         "checkpoint directory (bit-exact continuation: "
                         "restores the panel state, rng streams, schedule "
                         "rng and round counter); starts fresh when the "
                         "directory is empty")
    ap.add_argument("--die-after-segments", type=int, default=0,
                    help="fault-injection harness hook: SIGKILL the "
                         "process after N segments (checkpoints, if "
                         "enabled, are flushed first)")
    ap.add_argument("--telemetry", action="store_true",
                    help="per-agent (S, m) metric panels from the segment "
                         "scan (loss, grad norm, distance-to-mean, "
                         "liveness, exact codec wire bytes) recorded on "
                         "each round event; same single device_get per "
                         "segment, bit-identical trajectory")
    ap.add_argument("--events", default="",
                    help="deterministic JSONL event stream path (+ a "
                         ".wall.jsonl wall-clock sidecar); default "
                         "OUT/events_<tag>.jsonl when --telemetry is on, "
                         "else console-only. Resume-safe: the stream is "
                         "truncated to the checkpointed seq so baseline "
                         "and kill+resume runs emit byte-identical files")
    ap.add_argument("--snapshot", default="",
                    help="periodic JSON telemetry snapshot path "
                         "(telemetry.SnapshotExporter riding the event "
                         "log's sink; rewritten atomically each round)")
    ap.add_argument("--profile", default="",
                    help="capture a jax profiler trace of the training "
                         "loop into this logdir (view with tensorboard/"
                         "xprof), with host spans train.sample, "
                         "train.dispatch, train.fetch, train.eval and "
                         "train.checkpoint; a profiler that cannot start is "
                         "an error")
    args = ap.parse_args(argv)

    cfg = preset_config(get_config(args.arch), args.preset)
    m = args.agents
    if args.preset == "cpu":
        cfg = cfg.replace(dist=dataclasses.replace(cfg.dist,
                                                   agents_per_pod=m))
    model = build_model(cfg)
    opt = make_optimizer(args.optimizer, args.lr, weight_decay=5e-4,
                         total_steps=args.rounds * args.local_steps)

    mesh = build_mesh(args.mesh, args.preset, cfg)
    batch_sharding = None
    if mesh is not None:
        rows = mesh_mod.num_agents(mesh)
        if m % rows:
            raise SystemExit(f"--agents {m} must be divisible by the mesh's "
                             f"pod*agent = {rows} so panel rows shard evenly")
        # (S, H, m, b, ...) batches: agent rows on the communication axes
        batch_sharding = NamedSharding(mesh, P(None, None, ("pod", "agent")))
        print(f"panel sharded on mesh {dict(mesh.shape)}")

    plan = (faults_mod.FaultPlan.parse(m, args.faults)
            if args.faults else None)

    # the schedule carries the merge operator of its global rounds; the
    # engine consumes it via the spec — sched.merger is the single source
    kw = {"prob": 0.2, "seed": args.seed, "merger": args.merge}
    if args.schedule == "windowed":
        kw.update(start=args.window_start, end=args.window_end or
                  args.rounds // 10)
    if plan is not None:
        kw["faults"] = plan
    sched = make_schedule(args.schedule, m, args.rounds, **kw)
    seg_len = 1 if args.schedule == "adaptive" else max(1, args.segment)

    if args.schedule == "adaptive" and (args.checkpoint_every or
                                        args.resume):
        raise SystemExit(
            "--checkpoint-every/--resume do not support the adaptive "
            "schedule: its controller state is host-side feedback that a "
            "checkpoint cannot replay bit-exactly")

    tag = f"{args.arch}_{args.schedule}_a{args.alpha}"
    if args.merge != "uniform":
        tag += f"_m{args.merge}"
    if args.residency:
        tag += "_r" + args.residency.replace("=", "").replace(",", "_")

    # the run configuration that DEFINES the trajectory (the checkpoint
    # fingerprint keys): checkpoint/resume/telemetry plumbing is excluded
    # so a baseline and its kill+resume twin share one run_id
    run_cfg = {k: vars(args)[k] for k in (
        "arch", "preset", "agents", "rounds", "local_steps", "batch",
        "seq", "segment", "schedule", "window_start", "window_end",
        "optimizer", "lr", "alpha", "wire", "residency", "merge",
        "eval_merged_every", "seed", "faults")}
    run_id = telemetry.make_run_id(run_cfg)
    events_path = args.events or (
        os.path.join(args.out, f"events_{tag}.jsonl")
        if args.telemetry else None)

    ckpt = None
    if args.checkpoint_every or args.resume:
        # the residency stamp guards --resume against decoding a v2
        # blob's stored-layout panels with a different --residency
        ckpt = Checkpointer(
            args.checkpoint_dir or os.path.join(args.out, "ckpt_" + tag),
            keep=args.checkpoint_keep, fingerprint=run_cfg,
            residency=parse_policy(args.residency or None))

    key = jax.random.PRNGKey(args.seed)
    state, spec = dsgd.init_panel_state(model.init_params, opt, m, key,
                                        mesh=mesh, wire=args.wire,
                                        merger=sched.merger,
                                        residency=args.residency or None)
    print(f"wire codec {args.wire}: {spec.wire_payload_bytes} B/agent "
          f"payload ({spec.wire_total_bytes} B with scales/indices) per "
          f"full-panel exchange; merge operator {spec.merger}")
    fused = {"auto": None, "on": True, "off": False}[args.fused_moments]
    fused_active = fused_moments_auto(spec, opt) if fused is None else fused
    res_bytes = resident_bytes_model(spec, opt, fused=fused_active)
    print(f"residency {args.residency or 'f32'}: "
          f"{res_bytes['total']} B/agent resident "
          f"(params {res_bytes['params']}, moments {res_bytes['moments']}, "
          f"wire_err {res_bytes['wire_err']}, "
          f"merge_stat {res_bytes['merge_stat']}); "
          f"peak {res_bytes['peak']} B/agent "
          f"(+{res_bytes['transient_bytes']} transient); "
          f"fused moments {'on' if fused_active else 'off'}")
    segment_fn = dsgd.make_panel_segment(model.loss_fn, opt,
                                         args.local_steps, spec,
                                         fused=fused,
                                         telemetry=args.telemetry)

    lm = SyntheticLM(vocab=cfg.vocab_size, num_domains=8, seed=args.seed)
    mixtures = lm.domain_mixtures(m, args.alpha, seed=args.seed + 1)
    rng_np = np.random.default_rng(args.seed + 2)

    def eval_loss(params, batches):
        l, _ = model.loss_fn(params, batches, None)
        return l

    # counterfactual merged-model eval under the run's merge operator
    # (var/fisher/swa read the engine's merge_stat panels); the panel
    # variant keeps every op constrained to the spec's mesh layout.
    # ``lv`` masks dead agents out of both the merge and the local mean
    # when a fault plan is active
    eval_merged = jax.jit(
        lambda pan, mstat, b, lv: merge_mod.counterfactual_eval_panel(
            lambda p: eval_loss(p, b), pan, spec, stats=mstat, live=lv))

    def _local_mean(pan, b, lv):
        if spec.sharded:  # rows on different devices: one vmapped program
            losses = jax.vmap(eval_loss, in_axes=(0, None))(
                panel_mod.from_panel(pan, spec), b)
        else:  # agent by agent, as dsgd's local step (compile cost)
            losses = jax.lax.map(
                lambda row: eval_loss(panel_mod.from_panel(row, spec), b),
                pan)
        if lv is None:
            return jnp.mean(losses)
        lf = lv.astype(jnp.float32)
        return jnp.sum(losses * lf) / jnp.maximum(jnp.sum(lf), 1.0)

    eval_local = jax.jit(_local_mean)

    def alive_after(r):
        """(m,) bool of agents holding a usable model after round ``r``,
        or None without a fault plan (dead agents' rows are stale
        pass-through and excluded from evals)."""
        if plan is None:
            return None
        return jnp.asarray(plan.mask(r) >= faults_mod.LIVE)

    # a fixed GLOBAL eval batch (uniform domain mixture = global dist)
    glob_mix = np.ones(lm.num_domains) / lm.num_domains
    eval_batch = jax.tree.map(jnp.asarray, {
        k: v[0] for k, v in make_agent_lm_batches(
            lm, [glob_mix], 2 * args.batch, args.seq,
            np.random.default_rng(999)).items()})

    history = []
    monitor = {}
    comm_cost = 0.0
    t = 0
    seg_idx = 0
    resume_seq = None
    if args.resume and ckpt is not None:
        rec = ckpt.restore_latest({"state": state, "key": key})
        if rec is None:
            print("resume: no checkpoint found, starting fresh")
        else:
            step, tree, meta = rec
            if mesh is not None:
                tree["state"] = jax.device_put(
                    tree["state"],
                    dsgd.panel_state_shardings(state, spec))
                tree["key"] = jax.device_put(jnp.asarray(tree["key"]))
            else:
                tree = jax.tree.map(jnp.asarray, tree)
            state, key = tree["state"], tree["key"]
            t = int(meta["round"])
            seg_idx = int(meta["segments"])
            comm_cost = float(meta["comm_cost"])
            monitor = meta["monitor"]
            history = meta["history"]
            rng_np.bit_generator.state = meta["data_rng"]
            sched.rng.bit_generator.state = meta["sched_rng"]
            resume_seq = meta.get("events_seq")
            print(f"resumed from checkpoint step {step} (round {t})")

    # the event log: deterministic stream (+ wall sidecar) when a path is
    # set, console/validation-only otherwise. On resume the stream is
    # truncated back to the checkpointed seq — replayed rounds are
    # re-emitted exactly once, keeping baseline vs kill+resume streams
    # byte-identical (scripts/fault_smoke.py pins this)
    snap = (telemetry.SnapshotExporter(args.snapshot)
            if args.snapshot else None)
    log = telemetry.EventLog(
        events_path, run_id=run_id,
        resume_at=resume_seq if events_path else None, sink=snap)
    if resume_seq is None:
        print(telemetry.format_event(log.emit(
            "run_start", run_id=run_id, schema=telemetry.SCHEMA_VERSION,
            config=run_cfg)), flush=True)
    else:
        log.emit_op("resume", round=t, segments=seg_idx, seq=log.seq)
    if ckpt is not None:
        ckpt.events = log  # sidecar checkpoint_save records
    prof = telemetry.profile_trace(args.profile,
                                   enabled=bool(args.profile)).start()
    if prof:
        log.emit_op("profile_start", logdir=args.profile)
    t0 = time.time()
    ev = args.eval_merged_every
    while t < args.rounds:
        S = min(seg_len, args.rounds - t)
        if ev > 0:  # chop segments at the eval cadence so the merged
            # counterfactual is measured exactly every ``ev`` rounds
            S = min(S, (t // ev + 1) * ev - t)
        pad = seg_len - S  # tail segment: pad to the common length so the
        # jitted scan is compiled ONCE (padded rounds are masked no-ops)
        Ws, comm_after, glob, lives = [], [], [], []
        for s in range(S):
            W = sched.mixing_matrix(t + s, monitor)
            comm_cost += sched.round_cost(W)
            comm_after.append(comm_cost)
            Ws.append(W)
            # the schedule KNOWS which rounds are global — tell the
            # engine explicitly instead of fingerprinting W (a gossip
            # matrix can coincide with the 1/m average at small m)
            glob.append(sched.last_kind == "global")
            lives.append(sched.last_live if sched.last_live is not None
                         else np.ones(m, np.int8))
        glob_host = list(glob)
        Ws += [np.eye(m)] * pad
        glob += [False] * pad
        lives += [np.ones(m, np.int8)] * pad
        Ws = jnp.asarray(np.stack(Ws), jnp.float32)
        glob = jnp.asarray(glob)
        live = (jnp.asarray(np.stack(lives), jnp.int32)
                if plan is not None else None)
        with telemetry.annotate("train.sample"):
            batches = sample_segment_batches(lm, mixtures, S,
                                             args.local_steps, args.batch,
                                             args.seq, rng_np)
            if pad:
                batches = {k: jnp.concatenate(
                    [v, jnp.zeros((pad,) + v.shape[1:], v.dtype)])
                    for k, v in batches.items()}
            if batch_sharding is not None:
                batches = {k: jax.device_put(v, batch_sharding)
                           for k, v in batches.items()}
        active = jnp.asarray([True] * S + [False] * pad)
        key, k = jax.random.split(key)
        seg_t0 = time.perf_counter()
        with telemetry.annotate("train.dispatch"):
            state, mets = segment_fn(state, batches, Ws, k, active, glob,
                                     live)
        with telemetry.annotate("train.fetch"):
            mets = jax.device_get(mets)  # ONE transfer for the segment
        mets = {k: v[:S] for k, v in mets.items()}
        monitor = {"grad_norm": float(mets["grad_norm"][-1]),
                   "consensus": float(mets["consensus"][-1])}
        # merged/local eval at the eval cadence (--eval-merged-every, or
        # every segment end when 0) and always at the final round
        do_eval = (ev == 0 or (t + S) % ev == 0 or t + S == args.rounds)
        merged_l = local_l = None
        if do_eval:
            with telemetry.annotate("train.eval"):
                lv_now = alive_after(t + S - 1)
                merged_l = float(eval_merged(state["panel"],
                                             state.get("merge_stat"),
                                             eval_batch, lv_now))
                local_l = float(eval_local(state["panel"], eval_batch,
                                           lv_now))
        rev = None
        for s in range(S):
            r = t + s
            if plan is not None:
                for agent, kind in plan.at(r):
                    log.emit("fault", round=r, agent=agent, kind=kind)
            extra = ({k: mets[k][s] for k in
                      ("loss_agent", "grad_norm_agent", "dist_to_mean",
                       "live", "wire_bytes")} if args.telemetry else {})
            rev = log.emit(
                "round", round=r, loss=float(mets["loss"][s]),
                grad_norm=float(mets["grad_norm"][s]),
                grad_norm_max=float(mets["grad_norm_max"][s]),
                consensus=float(mets["consensus"][s]),
                comm_cost_P=float(comm_after[s]),
                resident_bytes=int(res_bytes["total"]),
                transient_bytes=int(res_bytes["transient_bytes"]), **extra)
            if glob_host[s]:
                log.emit("merge", round=r, operator=spec.merger)
            # eval is measured once per segment (at its end); intermediate
            # rounds carry None so every record has the same schema
            last = s == S - 1
            history.append({"round": r,
                            "train_loss": float(mets["loss"][s]),
                            "consensus": float(mets["consensus"][s]),
                            "grad_norm": float(mets["grad_norm"][s]),
                            "merged_eval": merged_l if last else None,
                            "local_eval": local_l if last else None,
                            "comm_cost_P": comm_after[s]})
        t += S
        seg_idx += 1
        print(telemetry.format_event(rev), flush=True)
        if merged_l is not None:
            print(telemetry.format_event(log.emit(
                "eval", round=t - 1, merged_eval=merged_l,
                local_eval=local_l)), flush=True)
        log.emit_op("segment", seg=seg_idx, rounds=S,
                    dt=time.perf_counter() - seg_t0)
        if ckpt is not None and args.checkpoint_every and (
                seg_idx % args.checkpoint_every == 0 or t >= args.rounds):
            # async: the host snapshot happens before save() returns, so
            # the next segment is free to donate the live state.
            # events_seq checkpoints the deterministic stream's position —
            # the truncate-on-resume cursor
            with telemetry.annotate("train.checkpoint"):
                ckpt.save(t, {"state": state, "key": key}, block=False, meta={
                    "round": t, "segments": seg_idx, "comm_cost": comm_cost,
                    "monitor": monitor, "history": history,
                    "data_rng": rng_np.bit_generator.state,
                    "sched_rng": sched.rng.bit_generator.state,
                    "events_seq": log.seq})
        if args.die_after_segments and seg_idx >= args.die_after_segments:
            if ckpt is not None:
                ckpt.wait()
            print(f"fault injection: dying after segment {seg_idx} "
                  f"(round {t})", flush=True)
            os.kill(os.getpid(), signal.SIGKILL)
    if prof:
        prof.stop()
        log.emit_op("profile_stop", logdir=args.profile)
        print(f"profiler trace captured to {args.profile}")
    print(telemetry.format_event(log.emit(
        "run_end", rounds=args.rounds,
        final_loss=history[-1]["train_loss"] if history else 0.0,
        comm_cost_P=comm_cost)), flush=True)
    print(f"total {time.time()-t0:.1f}s")
    if ckpt is not None:
        ckpt.wait()
    log.close()
    if snap is not None:
        snap.close()
        print(f"telemetry snapshot: {args.snapshot}")
    if events_path:
        print(f"events: {events_path} (+ {telemetry.wall_path(events_path)})")

    os.makedirs(args.out, exist_ok=True)
    with open(os.path.join(args.out, tag + ".json"), "w") as f:
        json.dump({"args": vars(args), "history": history}, f, indent=1)
    # merge with the RUN'S operator (+ its stats), not the uniform mean —
    # the merged model must be the one whose merged_eval the history just
    # reported; under a fault plan only agents alive at the end contribute
    merged = merge_mod.merged_panel_tree(
        state["panel"], spec, stats=state.get("merge_stat"),
        live=alive_after(args.rounds - 1))
    if args.save_merged:
        save(args.save_merged, merged)
        print(f"saved {spec.merger}-merged model to", args.save_merged)
    return {"history": history, "merged": merged}


if __name__ == "__main__":
    main()
