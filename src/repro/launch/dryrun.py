"""Multi-pod dry-run driver (deliverable e) + roofline source (g).

For every (architecture x input-shape x mesh) this lowers + compiles the
real step function against ShapeDtypeStruct inputs (no allocation), records
``memory_analysis()`` / ``cost_analysis()`` / parsed collective bytes, and
derives the three roofline terms. Results land as one JSON per pair under
``results/dryrun/``; ``python -m benchmarks.roofline`` renders the table.

Variants (the §Perf levers; "baseline" is the paper-faithful config):
  baseline      dense-W einsum gossip, remat=full, f32 wire
  merge         psum global-merge round instead of dense W   (collective /m)
  nocomm        W=I round skipped on host (no mixing op at all)
  bf16wire      gossip in bf16                               (collective /2)
  pairwise      partner-gather pairwise gossip               (collective /m)
  remat_dots    remat policy dots_saveable                   (compute down)
  nochunk       un-chunked CE loss                           (memory up)
  panel         flat-panel segment engine, panels D-sharded over 'fsdp'
                (fused mix -> per-shard matmuls, fsdp-local collectives)
  panel_bf16wire  panel engine with a bf16 gossip payload
  panel_int8wire  panel engine with the int8 stochastic-rounding wire
                codec (repro.wire; modelled payload /4 on f32 groups via
                PanelSpec.wire_bytes — the SPMD collectives still move
                dequantized f32 shards today, see ROADMAP "True int8
                collectives")
  panel_int4wire  panel engine with the packed-nibble int4 wire codec
                (grouped scales; modelled payload /8 on f32 groups)
  panel_topkwire  panel engine with the top-k sparse-innovation codec
                (mirror panel as the EF state; the mix lowers to the
                delta form x + (W - I) @ mirror, not one dense matmul)
  panel_residency_int8  panel engine with the moments=int8 residency
                policy (repro.residency: grouped signed-sqrt companded
                int8 moment storage) — the record's memory_analysis and
                ``resident_bytes_per_agent`` extra show the per-agent
                HBM drop vs the plain panel variant

Run as a script, it compiles against 512 forced host (CPU) devices; the
flag is set only in ``__main__``, so importing this module leaves
``XLA_FLAGS`` alone.
"""
import argparse
import dataclasses
import json
import os
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs import INPUT_SHAPES, get_config, list_archs
from repro.core import dsgd
from repro.core import panel as panel_mod
from repro.launch import mesh as mesh_mod
from repro.models import build_model
from repro.models.sharding import (TRAIN_RULES, activation_sharding,
                                   resolve, serve_rules)
from repro.optim import make_optimizer
from repro.utils import flops as flops_mod
from repro.utils.hlo import collective_bytes

PEAK_FLOPS = 197e12  # bf16 / chip (v5e)
HBM_BW = 819e9  # B/s / chip
ICI_BW = 50e9  # B/s / link

ARCHS = ["gemma-2b", "phi3-mini-3.8b", "arctic-480b", "qwen2-vl-72b",
         "xlstm-1.3b", "seamless-m4t-medium", "deepseek-v3-671b",
         "recurrentgemma-2b", "olmo-1b", "yi-34b"]
# long_500k policy (DESIGN.md §5): run for sub-quadratic archs; gemma-2b uses
# its sliding-window variant; others are recorded SKIPs.
LONG_OK = {"xlstm-1.3b", "recurrentgemma-2b"}
LONG_VIA_SW = {"gemma-2b": "gemma-2b-sw"}


def _leaf_is_pspec(x):
    return isinstance(x, P)


def _named(mesh, ps_tree):
    return jax.tree.map(lambda ps: NamedSharding(mesh, ps), ps_tree,
                        is_leaf=_leaf_is_pspec)


def _batch_pspec(batch_shapes, lead_axes, mesh, inner_axis=None):
    """Shard leading batch dim(s); drop axes that don't divide."""
    def one(x):
        axes = [None] * len(x.shape)
        size = int(np.prod([mesh.shape[a] for a in lead_axes]))
        if x.shape and x.shape[0] % size == 0 and size > 1:
            axes[0] = lead_axes if len(lead_axes) > 1 else lead_axes[0]
        if inner_axis and len(x.shape) > 1:
            isz = mesh.shape[inner_axis]
            if x.shape[1] % isz == 0 and isz > 1:
                axes[1] = inner_axis
        return P(*axes)
    return jax.tree.map(one, batch_shapes)


def _variant_cfg(cfg, variant, scan=False):
    dist = cfg.dist
    if "dots" in variant:
        dist = dataclasses.replace(dist, remat="dots")
    if variant == "nochunk":
        dist = dataclasses.replace(dist, loss_chunk=1 << 30)
    if "flashxla" in variant:
        dist = dataclasses.replace(dist, attn_block=512)
    if "seqpar" in variant:
        dist = dataclasses.replace(dist, seq_shard=True)
    if "moeshard2" in variant:
        dist = dataclasses.replace(dist, moe_dispatch_shard="dmodel")
    elif "moeshard" in variant:
        dist = dataclasses.replace(dist, moe_dispatch_shard="tokens")
    dist = dataclasses.replace(dist, scan_layers=scan)
    return cfg.replace(dist=dist)


def build_train(cfg, shape, multi_pod, variant, scan=False):
    cfg = _variant_cfg(cfg, variant, scan=scan)
    model = build_model(cfg)
    mesh = mesh_mod.make_training_mesh(cfg.dist.agents_per_pod,
                                       multi_pod=multi_pod)
    m = mesh_mod.num_agents(mesh)
    opt = make_optimizer("adamw", 1e-4)
    key = jax.random.PRNGKey(0)
    state_shapes = jax.eval_shape(
        lambda k: dsgd.init_state(model.init_params, opt, m, k), key)
    params_ps = resolve(model.param_spec(), state_shapes["params"], mesh,
                        TRAIN_RULES, prefix=(("pod", "agent"),))
    state_ps = {"params": params_ps,
                "opt": {"m": params_ps, "v": params_ps, "step_count": P()},
                "step": P()}
    batch_shapes = model.input_specs(shape, agents=m)
    batch_ps = _batch_pspec(batch_shapes, ("pod", "agent"), mesh,
                            inner_axis="fsdp")

    impl = {"baseline": "dense", "merge": "merge", "nocomm": "none",
            "pairwise": "pairwise", "bf16wire": "dense"}.get(variant, "dense")
    wire = jnp.bfloat16 if variant == "bf16wire" else None

    if impl == "pairwise":
        def step(state, batch, partner, rng):
            # per-leaf variant: leaves carry heterogeneous shardings here,
            # so the panel path's concatenate would force resharding
            from repro.core.gossip import mix_pairwise_tree
            s = dsgd.make_dsgd_step(model.loss_fn, opt, gossip_impl="none",
                                    monitor=False)
            new_state, mets = s(state, batch, None, rng)
            new_state["params"] = mix_pairwise_tree(
                new_state["params"], partner, wire_dtype=wire)
            return new_state, mets
        w_sds = jax.ShapeDtypeStruct((m,), jnp.int32)
    else:
        step = dsgd.make_dsgd_step(model.loss_fn, opt, gossip_impl=impl,
                                   monitor=False, wire_dtype=wire)
        w_sds = jax.ShapeDtypeStruct((m, m), jnp.float32)

    key_sds = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    in_sh = (_named(mesh, state_ps), _named(mesh, batch_ps),
             NamedSharding(mesh, P()), NamedSharding(mesh, P()))
    fn = jax.jit(step, in_shardings=in_sh)
    args = (state_shapes, batch_shapes, w_sds, key_sds)
    return fn, args, mesh, TRAIN_RULES, {"agents": m}


def build_train_panel(cfg, shape, multi_pod, variant, scan=True):
    """Flat-panel segment engine on the training mesh: the (m, D) panels are
    row-sharded over ('pod','agent') and D-sharded over 'fsdp'
    (core/panel.shard_spec), the per-leaf params/grads inside the local step
    keep their model-natural layouts via ``param_shardings``, and ONE
    S=1/H=1 segment is lowered so the record's collectives show the fused
    mix as per-shard matmuls + fsdp-local gossip traffic."""
    cfg = _variant_cfg(cfg, variant, scan=scan)
    model = build_model(cfg)
    mesh = mesh_mod.make_training_mesh(cfg.dist.agents_per_pod,
                                       multi_pod=multi_pod)
    m = mesh_mod.num_agents(mesh)
    opt = make_optimizer("adamw", 1e-4)
    key = jax.random.PRNGKey(0)

    wire = ("bf16" if "bf16wire" in variant
            else "int8" if "int8wire" in variant
            else "int4" if "int4wire" in variant
            else "topk" if "topkwire" in variant else None)
    residency = {"moments": "int8"} if "residency_int8" in variant else None
    params_sds = jax.eval_shape(
        lambda k: dsgd._init_agent_params(model.init_params, m, k, False),
        key)
    spec = panel_mod.shard_spec(panel_mod.make_spec(params_sds), mesh)
    if wire is not None:
        spec = panel_mod.with_wire(spec, wire)
    if residency is not None:
        spec = panel_mod.with_residency(spec, residency)
    state_sds = jax.eval_shape(
        lambda k: dsgd.init_panel_state(model.init_params, opt, m, k,
                                        wire=wire, residency=residency)[0],
        key)
    param_ps = resolve(model.param_spec(), params_sds, mesh, TRAIN_RULES,
                       prefix=(("pod", "agent"),))
    param_sh = _named(mesh, param_ps)

    batch_shapes = model.input_specs(shape, agents=m)
    batch_ps = _batch_pspec(batch_shapes, ("pod", "agent"), mesh,
                            inner_axis="fsdp")
    # (S=1, H=1) segment wrapping: two leading scan dims, replicated
    seg_batch = jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((1, 1) + s.shape, s.dtype),
        batch_shapes)
    seg_batch_ps = jax.tree.map(lambda ps: P(None, None, *ps), batch_ps,
                                is_leaf=_leaf_is_pspec)

    in_sh = (dsgd.panel_state_shardings(state_sds, spec),
             _named(mesh, seg_batch_ps),
             NamedSharding(mesh, P()), NamedSharding(mesh, P()))
    fn = dsgd.make_panel_segment(model.loss_fn, opt, 1, spec,
                                 param_shardings=param_sh,
                                 in_shardings=in_sh)
    w_sds = jax.ShapeDtypeStruct((1, m, m), jnp.float32)
    key_sds = jax.eval_shape(lambda: jax.random.PRNGKey(0))
    args = (state_sds, seg_batch, w_sds, key_sds)
    from repro.telemetry.metrics import resident_bytes_model
    res = resident_bytes_model(spec, opt)
    return fn, args, mesh, TRAIN_RULES, {"agents": m,
                                         "panel_width": spec.width,
                                         "wire_bytes_per_agent":
                                             spec.wire_bytes,
                                         "resident_bytes_per_agent": res}


def build_serve(cfg, shape, multi_pod, variant):
    cfg = _variant_cfg(cfg, variant)
    cfg = cfg.replace(param_dtype="bfloat16", compute_dtype="bfloat16",
                      dist=dataclasses.replace(cfg.dist, remat="none"))
    model = build_model(cfg)
    mesh = mesh_mod.make_production_mesh(multi_pod=multi_pod)
    big = cfg.dist.agents_per_pod < 16  # >30B params: FSDP the weights too
    rules = serve_rules(mesh, big=big)
    data_axes = ("pod", "data") if multi_pod else ("data",)
    key = jax.random.PRNGKey(0)
    params_shapes = jax.eval_shape(model.init_params, key)
    params_ps = resolve(model.param_spec(), params_shapes, mesh, rules)
    inputs = model.input_specs(shape, dtype=jnp.bfloat16)

    if shape.kind == "prefill":
        def step(params, batch):
            return model.prefill(params, batch, max_len=shape.seq_len)
        batch_ps = _batch_pspec(
            {k: v for k, v in inputs.items()}, data_axes, mesh)
        fn = jax.jit(step, in_shardings=(_named(mesh, params_ps),
                                         _named(mesh, batch_ps)))
        args = (params_shapes, inputs)
    else:  # decode
        caches_shapes = inputs["caches"]
        cache_ps = resolve(model.cache_spec(), caches_shapes, mesh, rules)
        tok_ps = _batch_pspec(
            {"tokens": inputs["tokens"]}, data_axes, mesh)["tokens"]

        def step(params, caches, tokens, index):
            return model.decode_step(params, caches, tokens, index)
        fn = jax.jit(step, in_shardings=(
            _named(mesh, params_ps), _named(mesh, cache_ps),
            NamedSharding(mesh, tok_ps), NamedSharding(mesh, P())))
        args = (params_shapes, caches_shapes, inputs["tokens"],
                jax.ShapeDtypeStruct((), jnp.int32))
    return fn, args, mesh, rules, {"big": big}


HEAVY_TRAIN_LAYERS = 30


def _compile_train(cfg, shape, multi_pod, variant, scan):
    """Build + compile one train step; returns (compiled, mesh, extra)."""
    fn, args, mesh, rules, extra = build_train(cfg, shape, multi_pod,
                                               variant, scan=scan)
    with activation_sharding(mesh, rules):
        lowered = fn.lower(*args)
    return lowered.compile(), mesh, extra


def run_train_extrapolated(cfg, shape, multi_pod, variant, rec):
    """Heavy archs (>=30 layers): unrolled compiles are too slow on this
    1-core CPU container, and scanned compiles undercount while-loop bodies
    in cost_analysis. Instead: compile the SAME step with n=1 and n=2 main
    periods unrolled (fast), extrapolate per-period costs linearly to the
    full depth, and take memory_analysis from the scanned full-depth compile
    (loop-carried liveness is representative there). Marked
    ``extrapolated: true`` in the record."""
    period = len(cfg.layer_period)
    front = cfg.dense_ff_first_k
    n_main = (cfg.num_layers - front) // period
    assert (cfg.num_layers - front) % period == 0, "heavy arch has tail"

    def with_reps(n):
        return cfg.replace(num_layers=front + period * n)

    t0 = time.time()
    c1, mesh, extra = _compile_train(with_reps(1), shape, multi_pod, variant,
                                     scan=False)
    c2, _, _ = _compile_train(with_reps(2), shape, multi_pod, variant,
                              scan=False)
    cfull, _, _ = _compile_train(cfg, shape, multi_pod, variant, scan=True)
    rec.update(extra)
    rec["chips"] = mesh.devices.size
    rec["extrapolated"] = True
    rec["compile_s"] = round(time.time() - t0, 2)

    def costs(c):
        ca = c.cost_analysis() or {}
        _, coll, _ = collective_bytes(c.as_text())
        return (float(ca.get("flops", 0.0)),
                float(ca.get("bytes accessed", 0.0)), float(coll))

    f1, b1, g1 = costs(c1)
    f2, b2, g2 = costs(c2)
    lin = lambda a1, a2: a1 + (n_main - 1) * (a2 - a1)  # noqa: E731
    hlo_flops, hlo_bytes, coll_total = lin(f1, f2), lin(b1, b2), lin(g1, g2)
    rec["cost"] = {"flops_per_device": hlo_flops,
                   "bytes_per_device": hlo_bytes,
                   "per_period": {"flops": f2 - f1, "bytes": b2 - b1,
                                  "coll": g2 - g1}}
    per_kind1 = collective_bytes(c1.as_text())[0]
    per_kind2 = collective_bytes(c2.as_text())[0]
    per_kind = {k: int(lin(per_kind1.get(k, 0), per_kind2.get(k, 0)))
                for k in set(per_kind1) | set(per_kind2)}
    rec["collectives"] = {"bytes_per_device": coll_total,
                          "per_kind": per_kind,
                          "counts": collective_bytes(c2.as_text())[2]}

    ma = cfull.memory_analysis()
    rec["memory"] = {
        "argument_bytes": int(ma.argument_size_in_bytes),
        "output_bytes": int(ma.output_size_in_bytes),
        "temp_bytes": int(ma.temp_size_in_bytes),
        "alias_bytes": int(ma.alias_size_in_bytes),
    }
    per_dev_total = (ma.argument_size_in_bytes + ma.output_size_in_bytes
                     + ma.temp_size_in_bytes - ma.alias_size_in_bytes)
    rec["memory"]["per_device_total"] = int(per_dev_total)
    rec["memory"]["fits_16gb"] = bool(per_dev_total < 16e9)
    return rec, hlo_flops, hlo_bytes, coll_total, mesh.devices.size


def roofline_terms(hlo_flops, hlo_bytes, coll_bytes, chips):
    return {
        "compute_s": hlo_flops / PEAK_FLOPS,
        "memory_s": hlo_bytes / HBM_BW,
        "collective_s": coll_bytes / ICI_BW,
    }


def run_pair(arch, shape_name, multi_pod, variant="baseline", outdir=None):
    mesh_name = "2x16x16" if multi_pod else "16x16"
    tag = f"{arch}_{shape_name}_{mesh_name}_{variant}"
    shape = INPUT_SHAPES[shape_name]
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "variant": variant, "status": "OK"}
    t0 = time.time()
    try:
        eff_arch = arch
        if shape_name == "long_500k":
            if arch in LONG_VIA_SW:
                eff_arch = LONG_VIA_SW[arch]
                rec["note"] = "sliding-window variant (window=4096)"
            elif arch not in LONG_OK:
                rec["status"] = "SKIP"
                rec["reason"] = ("full quadratic attention family; long_500k "
                                 "reserved for sub-quadratic archs "
                                 "(DESIGN.md §5)")
                rec["wall_s"] = round(time.time() - t0, 2)
                _dump(rec, tag, outdir)
                return rec
        cfg = get_config(eff_arch)
        is_panel = variant.startswith("panel")
        if (shape.kind == "train" and not is_panel
                and cfg.num_layers >= HEAVY_TRAIN_LAYERS):
            rec, hlo_flops, hlo_bytes, coll_total, chips = (
                run_train_extrapolated(cfg, shape, multi_pod, variant, rec))
        else:
            if shape.kind == "train":
                # panel variants lower the fused segment engine directly
                # (scan-over-layers; no unrolled extrapolation pass)
                build = build_train_panel if is_panel else build_train
            else:
                build = build_serve
            fn, args, mesh, rules, extra = build(cfg, shape, multi_pod,
                                                 variant)
            rec.update(extra)
            chips = mesh.devices.size
            rec["chips"] = chips

            with activation_sharding(mesh, rules):
                lowered = fn.lower(*args)
            t1 = time.time()
            compiled = lowered.compile()
            t2 = time.time()
            rec["lower_s"] = round(t1 - t0, 2)
            rec["compile_s"] = round(t2 - t1, 2)

            ma = compiled.memory_analysis()
            rec["memory"] = {
                "argument_bytes": int(ma.argument_size_in_bytes),
                "output_bytes": int(ma.output_size_in_bytes),
                "temp_bytes": int(ma.temp_size_in_bytes),
                "alias_bytes": int(ma.alias_size_in_bytes),
            }
            per_dev_total = (ma.argument_size_in_bytes
                             + ma.output_size_in_bytes
                             + ma.temp_size_in_bytes
                             - ma.alias_size_in_bytes)
            rec["memory"]["per_device_total"] = int(per_dev_total)
            rec["memory"]["fits_16gb"] = bool(per_dev_total < 16e9)

            ca = compiled.cost_analysis() or {}
            hlo_flops = float(ca.get("flops", 0.0))
            hlo_bytes = float(ca.get("bytes accessed", 0.0))
            rec["cost"] = {"flops_per_device": hlo_flops,
                           "bytes_per_device": hlo_bytes}

            txt = compiled.as_text()
            per_kind, coll_total, counts = collective_bytes(txt)
            rec["collectives"] = {"bytes_per_device": coll_total,
                                  "per_kind": per_kind, "counts": counts}

        model = build_model(get_config(eff_arch))
        mf = flops_mod.model_flops(model, shape)
        rec["model_flops"] = mf
        terms = roofline_terms(hlo_flops, hlo_bytes, coll_total, chips)
        rec["roofline"] = terms
        dom = max(terms, key=terms.get)
        rec["roofline"]["dominant"] = dom
        total_hlo = hlo_flops * chips
        rec["roofline"]["useful_flops_ratio"] = (
            (mf["model_flops"] + mf["attn_flops"]) / total_hlo
            if total_hlo else None)
    except Exception as e:  # noqa: BLE001
        rec["status"] = "FAIL"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
    rec["wall_s"] = round(time.time() - t0, 2)
    _dump(rec, tag, outdir)
    return rec


def _dump(rec, tag, outdir):
    if outdir:
        os.makedirs(outdir, exist_ok=True)
        with open(os.path.join(outdir, tag + ".json"), "w") as f:
            json.dump(rec, f, indent=1, default=str)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--skip-existing", action="store_true")
    args = ap.parse_args()
    archs = ARCHS if args.arch == "all" else [args.arch]
    shapes = list(INPUT_SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]
    ok = fail = skip = 0
    for arch in archs:
        for shp in shapes:
            for mp in meshes:
                mesh_name = "2x16x16" if mp else "16x16"
                path = os.path.join(
                    args.out, f"{arch}_{shp}_{mesh_name}_{args.variant}.json")
                if args.skip_existing and os.path.exists(path):
                    with open(path) as f:
                        prev = json.load(f)
                    if prev.get("status") in ("OK", "SKIP"):
                        print(f"[keep] {arch} {shp} {mesh_name}", flush=True)
                        ok += prev["status"] == "OK"
                        skip += prev["status"] == "SKIP"
                        continue
                rec = run_pair(arch, shp, mp, args.variant, args.out)
                st = rec["status"]
                ok += st == "OK"
                fail += st == "FAIL"
                skip += st == "SKIP"
                dom = rec.get("roofline", {}).get("dominant", "-")
                print(f"[{st:4s}] {arch:22s} {shp:12s} "
                      f"{'2x16x16' if mp else '16x16':8s} {args.variant:9s} "
                      f"dom={dom} wall={rec['wall_s']}s"
                      + (f" err={rec.get('error','')[:100]}"
                         if st == 'FAIL' else ""), flush=True)
    print(f"done: ok={ok} fail={fail} skip={skip}")
    if fail:
        raise SystemExit(1)


if __name__ == "__main__":
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    main()
