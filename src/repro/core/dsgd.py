"""Decentralized training engine (Algorithm 1 of the paper).

Two state layouts:

* **Tree state** (:func:`init_state` + :func:`make_dsgd_step` /
  :func:`make_dsgd_round`) — every leaf of params/opt_state carries a
  leading (m,) agent axis (sharded over ('pod','agent') on the production
  mesh). Mixing is per-leaf (``gossip.*_tree``): the right lowering when
  leaves carry heterogeneous shardings (launch/dryrun.py), and the
  reference baseline for the panel engine.

* **Panel state** (:func:`init_panel_state` + :func:`make_panel_segment`)
  — params and optimizer moments live as persistent per-dtype (m, D)
  panels (core/panel.py). The segment driver scans a whole SCHEDULE
  SEGMENT of rounds on device (mixing matrices precomputed and stacked),
  donates the state buffers (in-place update, no per-round host
  dispatch), mixes with ONE fused matmul per dtype group, and returns
  per-round metrics as stacked arrays — a single device_get per segment.
  This is the hot path used by launch/train.py and benchmarked in
  benchmarks/panel_bench.py.

One round = per-agent local step(s) (per-agent grad + optimizer; zero
cross-agent traffic) followed by gossip mixing with the scheduler's W^(t).

``loss_fn(params, batch, rng) -> (loss, aux)`` is any per-agent objective
(an LM from repro.models, or the benchmark classifiers).
"""
from __future__ import annotations

from typing import Callable, Optional

import jax
import jax.numpy as jnp

from repro import merging as merging_mod
from repro import residency as residency_mod
from repro import wire as wire_mod
from repro.kernels import opt_fused as opt_fused_mod
from repro.core import gossip
from repro.core import panel as panel_mod
from repro.core.consensus import consensus_distance_tree
from repro.optim.optim import Optimizer
from repro.telemetry import metrics as tmetrics
from repro.telemetry.trace import scope


def _init_agent_params(init_params: Callable, m: int, rng,
                       same_init: bool):
    """``same_init=True`` matches the theory (theta_k^0 = theta^0); False
    matches the paper's main experiments (independent inits — the harder
    cross-initialization merge)."""
    if same_init:
        p = init_params(rng)
        return jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (m,) + x.shape), p)
    # agent by agent: the same bits as a vmap, and the TPU compiler takes
    # one agent's shapes (~1 s) instead of agent-stacked ones (~11 s per
    # olmo-1b FFN matrix)
    return jax.lax.map(init_params, jax.random.split(rng, m))


def _place(tree, shardings):
    """device_put (concrete) / sharding-constrain (traced) a pytree onto a
    matching tree of NamedSharding (panel_mod.place per leaf)."""
    return jax.tree.map(panel_mod.place, tree, shardings)


def init_state(init_params: Callable, optimizer: Optimizer, m: int, rng,
               same_init: bool = False, shardings=None):
    """Agent-stacked train state (see _init_agent_params for same_init).

    ``shardings`` (a pytree of NamedSharding matching the params tree,
    e.g. models.sharding.resolve(...) wrapped on a training mesh) places
    the params AND the parameter-shaped optimizer moments; step counters
    stay replicated."""
    params = _init_agent_params(init_params, m, rng, same_init)
    if shardings is not None:
        params = _place(params, shardings)
    opt_state = jax.vmap(optimizer.init)(params)
    if shardings is not None:
        opt_state = {k: (_place(v, shardings) if k in _MOMENT_KEYS else v)
                     for k, v in opt_state.items()}
    return {"params": params, "opt": opt_state,
            "step": jnp.zeros((), jnp.int32)}


# fold_in tag deriving the wire-codec key from a round's rng WITHOUT
# disturbing the local-step key schedule (so f32/bf16 runs stay bit-exact
# with the pre-codec engine, and idle rounds under any codec match them)
_WIRE_KEY_TAG = 0x77697265  # "wire"


def _wire_key(rng, needed: bool):
    return jax.random.fold_in(rng, _WIRE_KEY_TAG) if needed else None


def _tree_wire_check(wire) -> bool:
    """Validate a codec name for the tree-state drivers at build time
    (error feedback needs the panel engine's residual state); returns
    whether the codec draws a stochastic-rounding key."""
    if wire is None:
        return False
    codec = wire_mod.get_codec(wire)
    if codec.error_feedback:
        raise ValueError(
            f"codec '{codec.name}' needs an error-feedback residual; the "
            "tree-state drivers carry none — use the panel engine "
            "(make_panel_segment + init_panel_state(wire=...)) or 'int8'")
    return codec.needs_key


def _mix(params, W, impl: str, wire_dtype, wire=None, key=None):
    # Per-leaf mixing: tree-state steps are the sharding-aware reference
    # path (see module docstring); the fused panel path is make_panel_segment.
    # For impl == "pairwise" the step's W argument IS the (m,) int32
    # partner array (see topology.partner_array), not an (m, m) matrix.
    if impl == "dense":
        if wire_dtype is None and wire is None:
            return gossip.mix_dense_tree(params, W)
        # W == I rounds communicate nothing, so no codec may touch the
        # state (mirrors the panel engine's idle guard; pairwise idles
        # per-row inside mix_pairwise_tree)
        m = jax.tree.leaves(params)[0].shape[0]
        idle = jnp.all(W == jnp.eye(m, dtype=W.dtype))
        return jax.lax.cond(
            idle, lambda p: p,
            lambda p: gossip.mix_dense_tree(p, W, wire_dtype, wire, key),
            params)
    if impl == "pairwise":
        return gossip.mix_pairwise_tree(params, W, wire_dtype=wire_dtype,
                                        wire=wire, key=key)
    if impl == "merge":
        return gossip.global_merge_tree(params, wire_dtype, wire, key)
    if impl == "none":
        return params
    raise ValueError(impl)


def make_dsgd_step(loss_fn: Callable, optimizer: Optimizer, *,
                   gossip_impl: str = "dense",
                   wire_dtype=None, wire=None, monitor: bool = True):
    """One communication round with ONE local step per agent.

    step(state, batch, W, rng) -> (state, metrics); batch leaves (m, b, ...).
    With gossip_impl="pairwise", pass the (m,) int32 partner array as W.
    ``wire`` names a codec from repro.wire for the gossip payload (the
    stochastic int8 codecs draw their key from the step rng via fold_in).
    Error-feedback codecs are panel-engine-only (the tree state carries
    no residual) and are refused here.
    """
    needs_key = _tree_wire_check(wire)

    def step(state, batch, W, rng):
        m = jax.tree.leaves(state["params"])[0].shape[0]
        rngs = jax.random.split(rng, m)

        def one(p, b, r):
            (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b, r)
            return g, l

        grads, losses = jax.vmap(one)(state["params"], batch, rngs)
        new_p, new_opt = jax.vmap(optimizer.update)(
            grads, state["opt"], state["params"])
        mixed = _mix(new_p, W, gossip_impl, wire_dtype, wire,
                     _wire_key(rng, needs_key))
        metrics = {"loss": jnp.mean(losses)}
        if monitor:
            gbar = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads)
            metrics["grad_norm"] = jnp.sqrt(sum(
                jnp.sum(jnp.square(x)) for x in jax.tree.leaves(gbar)))
            metrics["consensus"] = consensus_distance_tree(mixed)
        return {"params": mixed, "opt": new_opt,
                "step": state["step"] + 1}, metrics

    return step


def make_dsgd_round(loss_fn: Callable, optimizer: Optimizer, local_steps: int,
                    *, gossip_impl: str = "dense", wire_dtype=None,
                    wire=None, monitor: bool = True):
    """One communication round with H local steps (paper: H=100).

    step(state, batches, W, rng): batches leaves (H, m, b, ...) — scanned.
    ``wire`` as in :func:`make_dsgd_step` (error-feedback codecs refused).
    """
    needs_key = _tree_wire_check(wire)

    def round_fn(state, batches, W, rng):
        m = jax.tree.leaves(state["params"])[0].shape[0]

        def body(carry, xs):
            params, opt = carry
            batch, r = xs
            rngs = jax.random.split(r, m)

            def one(p, b, rr):
                (l, aux), g = jax.value_and_grad(
                    loss_fn, has_aux=True)(p, b, rr)
                return g, l

            grads, losses = jax.vmap(one)(params, batch, rngs)
            new_p, new_opt = jax.vmap(optimizer.update)(grads, opt, params)
            gbar = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads)
            gn = jnp.sqrt(sum(jnp.sum(jnp.square(x))
                              for x in jax.tree.leaves(gbar)))
            return (new_p, new_opt), (jnp.mean(losses), gn)

        rngs = jax.random.split(rng, local_steps)
        (p, o), (losses, gns) = jax.lax.scan(
            body, (state["params"], state["opt"]), (batches, rngs))
        mixed = _mix(p, W, gossip_impl, wire_dtype, wire,
                     _wire_key(rng, needs_key))
        # mean AND max over the round's H local steps: reporting gns[-1]
        # alone silently dropped a gradient spike at any earlier local
        # step (tests/test_telemetry.py pins the regression)
        metrics = {"loss": jnp.mean(losses), "grad_norm": jnp.mean(gns),
                   "grad_norm_max": jnp.max(gns)}
        if monitor:
            metrics["consensus"] = consensus_distance_tree(mixed)
        return {"params": mixed, "opt": o,
                "step": state["step"] + local_steps}, metrics

    return round_fn


# ---------------------------------------------------------------------------
# Flat-panel engine: persistent (m, D) state, donated + scanned rounds.
# ---------------------------------------------------------------------------

# Optimizer-state entries that are parameter-shaped moment trees (AdamW m/v,
# SGD momentum mu); everything else (step_count) passes through unchanged.
_MOMENT_KEYS = ("m", "v", "mu")

# fold_in tag deriving the storage-codec stochastic-rounding keys from a
# round/step rng WITHOUT disturbing the local-step or wire key schedules
# (a non-stochastic residency policy never folds, so f32/bf16 storage
# runs keep the pre-residency key schedule bit-exactly); each state kind
# then folds its own index so moments/stats/wire_err draw independent
# streams from the same rng
_RES_KEY_TAG = 0x68626d00  # "hbm\0"
_RES_KIND_IDX = {"moments": 0, "stats": 1, "wire_err": 2}


def _res_key(rng, kind: str, needed: bool):
    if not needed:
        return None
    return jax.random.fold_in(jax.random.fold_in(rng, _RES_KEY_TAG),
                              _RES_KIND_IDX[kind])


def _res_plan(spec):
    """{state kind: {dtype group: Storage}} — the static application
    table of the spec's residency policy. Storage codecs act on f32
    state only: moment panels mirror each group's native dtype, so only
    the 'float32' group's moments are stored; merge stats and EF
    residuals are f32 for EVERY group (Merger.init_stats /
    Codec.init_err build them f32), so those kinds store across all
    groups. Resolved once at build time — the plan is trace-static."""
    plan = {}
    for kind, name in spec.residency:
        st = residency_mod.get_storage(name)
        if kind == "moments":
            groups = [g for g, _ in spec.groups if g == "float32"]
        else:
            groups = [g for g, _ in spec.groups]
        if groups:
            plan[kind] = {g: st for g in groups}
    return plan


def _res_constrain(v, spec, k: str):
    """Sharding constraint for one group's state leaf: a stored dict
    pins q to the group layout and the scale sidecar to rows-only; a
    plain array takes the group constraint (panel_mod._constrain_group,
    a no-op on unsharded specs)."""
    if isinstance(v, dict):
        return {"q": panel_mod._constrain_group(v["q"], spec, k),
                "scale": panel_mod.place(v["scale"],
                                         spec.sidecar_sharding(k))}
    return panel_mod._constrain_group(v, spec, k)


def _res_read(stored, sts, *, use_pallas: bool = False):
    """Decode a stored state-panel group dict to its f32 compute view
    (groups without a storage entry pass through)."""
    return {k: (sts[k].read(v, use_pallas=use_pallas)
                if k in sts else v)
            for k, v in stored.items()}


def _res_write(panel, sts, key, spec=None, *, use_pallas: bool = False):
    """Encode an f32 state-panel group dict into storage (per-group SR
    keys via residency.storage_keys — sorted-group fold order, the
    _wire_keys discipline); ``spec`` adds the sharding constraints."""
    keys = residency_mod.storage_keys(sts, key)
    out = {}
    for k, v in panel.items():
        if k in sts:
            v = sts[k].write(v, key=keys[k], use_pallas=use_pallas)
        out[k] = _res_constrain(v, spec, k) if spec is not None else v
    return out


def _res_init(panel, sts):
    """Deterministic encode of a fresh state-panel group dict (state
    build / RESYNC re-init — reproducible without a key schedule)."""
    return {k: (sts[k].init(v) if k in sts else v)
            for k, v in panel.items()}


def _opt_read(opt, sts, mom_keys, *, use_pallas: bool = False):
    """Optimizer state -> its f32 compute view: moment entries decode
    through the storage, everything else (step_count) passes through."""
    return {k: (_res_read(v, sts, use_pallas=use_pallas)
                if k in mom_keys else v)
            for k, v in opt.items()}


def _opt_write(opt, sts, mom_keys, key, spec, *, use_pallas: bool = False):
    """Encode the updated f32 moments back into storage, one folded key
    per moment entry (sorted order) so m/v draw independent SR bits."""
    present = sorted(k for k in opt if k in mom_keys)
    out = dict(opt)
    for i, k in enumerate(present):
        mk = None if key is None else jax.random.fold_in(key, i)
        out[k] = _res_write(opt[k], sts, mk, spec, use_pallas=use_pallas)
    return out


def _fused_opt_update(gpan, opt, pan, optimizer, sts, spec, key, *,
                      use_pallas: bool = False):
    """Fused moment update: the stored int8 groups run the single-sweep
    Pallas kernel (kernels/opt_fused.py) — decode, the optimizer's
    shared elementwise core, and the SR re-encode all in VMEM, HBM
    touching only int8 q + scales. No f32 moment view is ever
    materialized, which is both the bandwidth win and the peak-memory
    fix (resident_bytes_model's ``transient_bytes`` term is zero on
    this path).

    Groups without a storage entry (non-f32 dtype groups) take the
    legacy vmapped ``optimizer.update`` on their rest-subtree — same
    expression, same step_count bookkeeping, bit-identical to the
    unfused engine. SR keys replicate ``_opt_write``'s folds exactly
    (fold_in(key, i) over sorted present moment entries, then
    ``storage_keys``'s sorted-group fold), so the fused ref path is the
    unfused decode->update->encode composition bit-for-bit.

    lr/bc1/bc2 come from ``optimizer.hyper`` on the per-agent (m,)
    step_count — agent rows diverge after a RESYNC re-init, so the bias
    corrections ride the kernel as (m, 1) columns."""
    from repro.wire.codec import _uniform
    count = opt["step_count"] + 1
    lr, bc1, bc2 = optimizer.hyper(count)
    present = sorted(k for k in opt if k in optimizer.moment_keys)
    gkeys = {k: residency_mod.storage_keys(
        sts, None if key is None else jax.random.fold_in(key, i))
        for i, k in enumerate(present)}
    rest = [k for k in pan if k not in sts]
    new_pan, new_m, new_v = {}, {}, {}
    if rest:
        sub = lambda d: {k: d[k] for k in rest}
        opt_r = {k: (sub(v) if k in optimizer.moment_keys else v)
                 for k, v in opt.items()}
        pan_r, opt_r = jax.vmap(optimizer.update)(
            sub(gpan), opt_r, sub(pan))
        new_pan.update(pan_r)
        new_m.update(opt_r["m"])
        new_v.update(opt_r["v"])
    for k in pan:
        if k not in sts:
            continue
        st = sts[k]
        um = _uniform(gkeys["m"][k], gpan[k].shape)
        uv = _uniform(gkeys["v"][k], gpan[k].shape)
        p2, qm2, sm2, qv2, sv2 = opt_fused_mod.adamw_fused_int8(
            gpan[k], pan[k],
            opt["m"][k]["q"], opt["m"][k]["scale"],
            opt["v"][k]["q"], opt["v"][k]["scale"],
            um, uv, lr, bc1, bc2, group=st.group, core=optimizer.core,
            transform_fwd=st.transform_fwd, transform_inv=st.transform_inv,
            use_pallas=use_pallas)
        new_pan[k] = p2
        new_m[k] = _res_constrain({"q": qm2, "scale": sm2}, spec, k)
        new_v[k] = _res_constrain({"q": qv2, "scale": sv2}, spec, k)
    new_pan = {k: new_pan[k] for k in pan}
    new_opt = dict(opt)
    new_opt["m"] = {k: new_m[k] for k in opt["m"]}
    new_opt["v"] = {k: new_v[k] for k in opt["v"]}
    new_opt["step_count"] = count
    return new_pan, new_opt


def _wire_needs_ef(spec) -> bool:
    return any(wire_mod.get_codec(name).error_feedback
               for _, name in spec.wire)


def _init_wire_err(pan, spec, sts=None):
    """Fresh spec-sharded error-feedback panels: each dtype group's codec
    seeds its own state (zeros for the quantization residuals, a copy of
    the panel for the topk mirror — Codec.init_err). ``sts`` (the
    residency plan's wire_err storages) encodes them deterministically."""
    werr = {k: wire_mod.get_codec(spec.wire_of(k)).init_err(v)
            for k, v in pan.items()}
    if sts:
        werr = _res_init(werr, sts)
    return {k: _res_constrain(v, spec, k) for k, v in werr.items()}


def _wire_needs_key(spec) -> bool:
    return any(wire_mod.get_codec(name).needs_key for _, name in spec.wire)


def _wire_has_delta(spec) -> bool:
    return any(getattr(wire_mod.get_codec(name), "delta_mix", False)
               for _, name in spec.wire)


def _init_merge_stats(pan, spec, sts=None):
    """Fresh, spec-sharded statistics panels for the spec's merge operator
    (None when the operator keeps no statistics). ``sts`` (the residency
    plan's stats storages) encodes them deterministically."""
    mg = merging_mod.get_merger(spec.merger)
    if not mg.stat_panels:
        return None
    out = {}
    for name, stat in mg.init_stats(pan).items():
        if sts:
            stat = _res_init(stat, sts)
        out[name] = {k: _res_constrain(v, spec, k)
                     for k, v in stat.items()}
    return out


def init_panel_state(init_params: Callable, optimizer: Optimizer, m: int,
                     rng, same_init: bool = False, mesh=None, wire=None,
                     merger=None, residency=None):
    """Panel train state: params AND optimizer moments as per-dtype (m, D)
    panels. Returns (state, spec); the static spec is what turns panels
    back into model pytrees. The optimizer transforms are elementwise, so
    they run directly on the panel leaves — no per-leaf dispatch.

    ``mesh`` shards the panels: rows over ('pod','agent'), D over 'fsdp'
    (panel_mod.shard_spec); the optimizer-moment panels mirror the
    parameter panel layout exactly.

    ``wire`` attaches a wire-codec policy to the spec (panel_mod.with_wire:
    a codec name for every dtype group, or a per-group dict). An
    error-feedback codec adds ``state["wire_err"]`` — one f32 panel per
    dtype group, laid out exactly like the parameter panel, seeded by the
    group's codec (Codec.init_err) and donated through the segment scan.
    For int8_ef/int4_ef that panel is the zero-initialised quantization
    residual; for the topk codec it is the MIRROR x̂ — the receive-side
    reconstruction every peer accumulates from past sparse innovations,
    seeded with a copy of the initial panel (one full-precision sync).

    ``merger`` names the merge operator global rounds apply
    (panel_mod.with_merger, repro.merging). A statistical operator
    (var/fisher/swa) adds ``state["merge_stat"]`` — its per-agent f32
    statistics panels, parameter-panel layout, donated through the scan
    and updated by the segment driver.

    ``residency`` attaches a storage-codec policy to the spec
    (panel_mod.with_residency, repro.residency — a {kind: storage} dict
    or a 'moments=int8,stats=bf16' policy string). The named state
    panels are allocated DIRECTLY in their stored representation
    (deterministic encode — int8/int8g panels become {'q', 'scale'}
    dicts with f32 scale sidecars); no resident f32 copy ever
    materializes, here or inside the segment."""
    params = _init_agent_params(init_params, m, rng, same_init)
    spec = panel_mod.make_spec(params)
    if mesh is not None:
        spec = panel_mod.shard_spec(spec, mesh)
    if wire is not None:
        spec = panel_mod.with_wire(spec, wire)
    if merger is not None:
        spec = panel_mod.with_merger(spec, merger)
    if residency is not None:
        spec = panel_mod.with_residency(spec, residency)
    plan = _res_plan(spec)
    pan = panel_mod.to_panel(params, spec)
    opt_state = jax.vmap(optimizer.init)(pan)
    mom_sts = plan.get("moments")
    if mom_sts:
        opt_state = {k: (_res_init(v, mom_sts)
                         if k in optimizer.moment_keys else v)
                     for k, v in opt_state.items()}
    if spec.sharded:
        opt_state = {k: ({g: _res_constrain(x, spec, g)
                          for g, x in v.items()}
                         if k in _MOMENT_KEYS else v)
                     for k, v in opt_state.items()}
    state = {"panel": pan, "opt": opt_state,
             "step": jnp.zeros((), jnp.int32)}
    if _wire_needs_ef(spec):
        state["wire_err"] = _init_wire_err(pan, spec, plan.get("wire_err"))
    mstat = _init_merge_stats(pan, spec, plan.get("stats"))
    if mstat is not None:
        state["merge_stat"] = mstat
    return state, spec


def panel_state_shardings(state, spec):
    """NamedSharding pytree for a panel train state on a sharded spec —
    the ``in_shardings`` a caller hands to jit when lowering the segment
    driver against ShapeDtypeStructs (launch/dryrun.py, sharded tests)."""
    assert spec.sharded, "panel_state_shardings needs a shard_spec'ed spec"
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    repl = NamedSharding(spec.mesh, P())

    def group_sh(panel_like):
        out = {}
        for k, v in panel_like.items():
            gs = spec.sharding(k) or repl
            if isinstance(v, dict):
                # stored rep: q follows the group layout, the scale
                # sidecar shards rows-only (PanelSpec.sidecar_sharding)
                out[k] = {"q": gs,
                          "scale": spec.sidecar_sharding(k) or repl}
            else:
                out[k] = gs
        return out

    opt = {k: (group_sh(v) if k in _MOMENT_KEYS
               else jax.tree.map(lambda _: repl, v))
           for k, v in state["opt"].items()}
    out = {"panel": group_sh(state["panel"]), "opt": opt, "step": repl}
    if "wire_err" in state:
        out["wire_err"] = group_sh(state["wire_err"])
    if "merge_stat" in state:
        out["merge_stat"] = {name: group_sh(v)
                             for name, v in state["merge_stat"].items()}
    return out


def panelize_state(state, spec):
    """Tree state (init_state) -> panel state (same numbers, encoded per
    the spec's residency policy). A spec with an error-feedback wire
    policy gets a fresh zero residual panel; a statistical merge
    operator gets fresh statistics panels."""
    plan = _res_plan(spec)
    mom_sts = plan.get("moments")

    def mom(v):
        p = panel_mod.to_panel(v, spec)
        if mom_sts:
            p = {k: _res_constrain(x, spec, k)
                 for k, x in _res_init(p, mom_sts).items()}
        return p

    opt = {k: (mom(v) if k in _MOMENT_KEYS else v)
           for k, v in state["opt"].items()}
    pan = panel_mod.to_panel(state["params"], spec)
    out = {"panel": pan, "opt": opt, "step": state["step"]}
    if _wire_needs_ef(spec):
        out["wire_err"] = _init_wire_err(pan, spec, plan.get("wire_err"))
    mstat = _init_merge_stats(pan, spec, plan.get("stats"))
    if mstat is not None:
        out["merge_stat"] = mstat
    return out


def unpanelize_state(state, spec):
    """Panel state -> tree state (same numbers up to storage precision —
    stored moments decode through their codec; the wire_err residual and
    merge_stat panels are panel-engine carries and are dropped)."""
    mom_sts = _res_plan(spec).get("moments")

    def mom(v):
        if mom_sts:
            v = _res_read(v, mom_sts)
        return panel_mod.from_panel(v, spec)

    opt = {k: (mom(v) if k in _MOMENT_KEYS else v)
           for k, v in state["opt"].items()}
    return {"params": panel_mod.from_panel(state["panel"], spec), "opt": opt,
            "step": state["step"]}


def _agents_in_turn(one: Callable, pan, batch, rngs, spec):
    """(grad panel, (m,) losses) of every agent's local step, the agents
    one after another on the device that holds every row of ``pan``:
    ``one(params, batch, rng) -> (grads, loss)`` runs on leaves sliced
    out of agent-stacked slabs (panel.to_slabs) of the whole panel, and
    only it sits under dsgd.fwd_bwd."""
    def agent(xs):
        slabs, b, r = xs
        params = jax.tree_util.tree_unflatten(
            spec.treedef,
            [x.reshape(ls.shape) for x, ls in zip(slabs, spec.leaves)])
        with scope("dsgd.fwd_bwd"):
            g, l = one(params, b, r)
        return [x.reshape(ls.slab)
                for x, ls in zip(jax.tree.leaves(g), spec.leaves)], l

    grads, losses = jax.lax.map(
        agent, (panel_mod.to_slabs(pan, spec), batch, rngs))
    return panel_mod.to_panel(grads, spec), losses


def make_panel_segment(loss_fn: Callable, optimizer: Optimizer,
                       local_steps: int, spec, *, wire_dtype=None,
                       monitor: bool = True, telemetry: bool = False,
                       use_pallas: bool = False, donate: bool = True,
                       fused=None,
                       param_shardings=None, in_shardings=None):
    """Donated, scanned panel driver: one dispatch per SCHEDULE SEGMENT.

    segment(state, batches, Ws, rng, active=None, global_rounds=None,
            live=None)
    -> (state, metrics) with
      batches leaves (S, H, m, b, ...)  — H DISTINCT batches per round,
      Ws (S, m, m)                      — precomputed mixing matrices,
      active (S,) bool or None          — padding mask (see below),
      global_rounds (S,) bool or None   — which rounds are GLOBAL (see
                                          Merge operators below),
      live (S, m) int or None           — per-round per-agent liveness
                                          (see Liveness below),
      metrics dict of (S,) arrays      — one device_get per segment.

    **Metrics.** ``loss`` and ``grad_norm``/``grad_norm_max`` are the
    per-round mean/max over the H local steps (the old driver reported
    only the FINAL local step's grad norm, hiding any earlier spike);
    ``monitor=True`` adds the consensus ``Xi``. ``telemetry=True``
    extends the scalars to per-agent (S, m) METRIC PANELS — stacked by
    the same scan, still one device_get per segment:

      loss_agent      (S, m) f32 — per-agent mean loss over the round,
      grad_norm_agent (S, m) f32 — per-agent mean grad l2 norm,
      dist_to_mean    (S, m) f32 — per-agent distance to the (live)
                                   panel mean after the mix: the
                                   consensus decomposition
                                   (Xi == sqrt(live-mean(dist**2))),
      live            (S, m) i32 — the round's DEAD/LIVE/RESYNC trits,
      wire_bytes      (S, m) i32 — exact codec wire bytes each agent
                                   paid (PanelSpec.wire_total_bytes
                                   model; idle rows 0, a delta codec's
                                   global round and RESYNC pulls at
                                   full-precision cost).

    All telemetry values are pure reads of arrays the round already
    materialized — the trajectory is bit-identical with telemetry on or
    off (pinned by tests/test_telemetry.py).

    ``jax.lax.scan`` runs the S rounds (each an inner scan over the H
    local steps) entirely on device; ``donate_argnums=(0,)`` lets XLA
    update the panel state in place instead of copying the full
    agent-stacked state every round. The dense-W fused matmul covers every
    scheduler (W=I for idle rounds, fully-connected for merge rounds), so
    a segment needs no host-side dispatch on the round kind.

    **Wire codecs.** The spec's wire policy (panel_mod.with_wire /
    init_panel_state(wire=...)) compresses the gossip payload; the legacy
    ``wire_dtype`` cast survives as an explicit override (not both). A
    stochastic codec (int8/int4) draws its per-round key by folding a
    fixed tag into the round rng, so the local-step key schedule — and
    therefore any non-stochastic run — is bit-identical to the pre-codec
    engine. An error-feedback codec (int8_ef/int4_ef residuals, the topk
    mirror) carries ``state["wire_err"]`` (from init_panel_state) through
    the scan as one more donated panel; it is updated only on
    communicating rounds — idle W = I rounds bypass the codec entirely
    for EVERY codec family, so the residual/mirror passes through
    untouched and the round stays bit-exact.

    **Folded consensus.** With ``monitor=True`` the per-round consensus
    mean rides the mixing matmul itself (an extra 1^T/m row on W —
    panel_mod.mix_dense_mean), so the monitor costs one deviation pass
    instead of a second full mean reduce. Idle (W == I) rounds skip the
    matmul entirely — no payload travels, no codec touches the state —
    and keep the standalone consensus_distance reduce.

    ``active`` lets the host pad a PARTIAL tail segment up to the common
    segment length instead of retracing/recompiling the whole scan for a
    one-off smaller S: rounds with ``active[s] == False`` are full no-ops
    (state passes through untouched, metrics report 0) and their
    Ws/batches entries are ignored.

    **Liveness (elastic runs).** ``live`` extends the per-round ``active``
    mask to a per-round PER-AGENT (S, m) trit mask (core.faults:
    DEAD=0 / LIVE=1 / RESYNC=2 — the launcher stacks
    ``Schedule.last_live``). LIVE agents run the round normally. A DEAD
    agent's parameter, moment, EF-residual and merge-statistics rows
    pass through the round bit-exactly: it takes no local steps (its
    rows of the per-agent grad/optimizer update are discarded — the rng
    stream is consumed identically, so survivors' draws match the
    fault-free run), and the caller must hand in the matching DEGRADED W
    (Schedule does: topology.degrade_to_live / fully_connected_live), so
    its row is an identity row and the per-row idle rule keeps every
    codec off it. A RESYNC agent (its rejoin round) takes no local steps
    either; after the round's mix it receives a full-precision pull of
    the live agents' post-mix mean, its optimizer-moment rows are
    reset to zero and its EF-residual / merge-statistics rows are
    re-initialized from the synced parameters (its own state is stale by
    construction) — survivors are never perturbed. Metrics average over
    the live agents; ``consensus`` is the live-only Xi. With a
    non-uniform merge operator under faults, pass ``global_rounds``
    explicitly — a degraded global W no longer fingerprints as the 1/m
    matrix. ``live=None`` keeps the engine byte-identical to the
    pre-liveness path.

    **Merge operators.** The spec's merge operator
    (panel_mod.with_merger / init_panel_state(merger=...), repro.merging)
    is applied on GLOBAL rounds (the paper's single final merging,
    windowed/periodic AllReduce rounds). ``global_rounds`` marks them
    explicitly — the launcher reads the schedule's own knowledge
    (Schedule.last_kind). When None, the driver falls back to
    fingerprinting W against the fully-connected 1/m matrix; that is
    correct for every scheduler-emitted global round, but a gossip
    topology can COINCIDE with the 1/m average (m=2 matched pair,
    3-agent ring) and would then be routed through the operator — pass
    the explicit mask when running non-uniform operators on such
    topologies. 'uniform' keeps the byte-for-byte pre-subsystem path:
    global rounds stay inside the same fused matmul as every other
    round. A non-uniform operator dispatches those rounds through
    ``merging.merge_panel`` (payload still wire-codec encoded; one merged
    row broadcast back), and a STATISTICAL operator (var/fisher/swa)
    carries its per-agent stats panels as ``state["merge_stat"]`` —
    donated through the scan and updated every local step
    (``update_local``: fisher sees the grad panel) and/or once per round
    (``update_round``: var/swa see the param panel).

    **Storage residency.** The spec's residency policy
    (panel_mod.with_residency / init_panel_state(residency=...),
    repro.residency) keeps the named state panels — optimizer moments,
    merge stats, the EF residual/mirror — in compressed storage (bf16,
    int8 + scale sidecars) for the WHOLE segment; the f32 compute view
    exists only transiently inside the round. Fusion points: moments
    decode immediately before the vmapped optimizer update and the
    updated moments encode back in the same donated local step (SR keys
    folded off the step rng via a residency tag — non-stochastic runs
    never fold, keeping the pre-residency key schedule bit-exact);
    stats decode once at round entry and encode once at round exit;
    the EF residual decodes/encodes strictly INSIDE the communicating
    branches, so idle (W == I) rounds pass the stored bits through
    verbatim. Composition with liveness is bit-predictable: DEAD rows
    keep their stored bits (q AND scale) unchanged through the round,
    RESYNC rows re-encode deterministically (Storage.init /
    Storage.zero_like) so a rejoin bit-matches a freshly initialised
    agent. An empty/f32 policy compiles the exact pre-residency trace.

    On a sharded ``spec`` (shard_spec / init_panel_state(mesh=...)) every
    fused op keeps the panels in their mesh layout, so mixing lowers to
    per-fsdp-shard matmuls with agent-axis collectives that carry only the
    local column shard. ``param_shardings`` (NamedSharding pytree matching
    the model params, agent-stacked) re-pins the rebuilt per-leaf params
    for the grad compute; ``in_shardings`` is forwarded to jax.jit for
    lowering against ShapeDtypeStructs."""
    if wire_dtype is not None and spec.wire:
        raise ValueError("pass either wire_dtype= (legacy cast) or a spec "
                         "wire policy (with_wire), not both")
    needs_key = wire_dtype is None and _wire_needs_key(spec)
    needs_ef = wire_dtype is None and _wire_needs_ef(spec)
    merger = merging_mod.get_merger(spec.merger)
    # a delta (mirror) codec must route GLOBAL rounds through
    # merging.merge_panel even for the uniform operator: the one-shot
    # merge is its full-bandwidth round (panel.global_merge delta rule)
    # and cannot stay inside the sparse damped fused matmul
    has_delta = wire_dtype is None and _wire_has_delta(spec)
    plain_merge = merger.name == "uniform" and not has_delta
    needs_stats = bool(merger.stat_panels)
    res_plan = _res_plan(spec)
    res_mom = res_plan.get("moments")
    res_stat = res_plan.get("stats")
    res_err = res_plan.get("wire_err")
    res_mom_key = bool(res_mom) and any(s.needs_key
                                        for s in res_mom.values())
    res_stat_key = bool(res_stat) and any(s.needs_key
                                          for s in res_stat.values())
    res_err_key = bool(res_err) and any(s.needs_key
                                        for s in res_err.values())
    res_pallas = panel_mod._pallas_ok(use_pallas, spec)
    mom_keys = tuple(optimizer.moment_keys)
    # fused moment update (kernels/opt_fused.py): None auto-enables
    # whenever the policy/optimizer qualify (grouped int8 moments +
    # optimizer.core), True requires it, False forces the unfused
    # decode->update->encode. The fused ref path is the unfused
    # composition bit-for-bit, so auto-on is trajectory-preserving.
    fused_ok = tmetrics.fused_moments_auto(spec, optimizer)
    if fused and not fused_ok:
        raise ValueError(
            "fused=True but the fused moment update does not apply: it "
            "needs a grouped-int8 moments storage (fused_update "
            f"capability; policy has '{spec.residency_of('moments')}') "
            "and an optimizer exposing core/hyper with (m, v) moments "
            f"(got '{optimizer.name}')")
    res_fused = fused_ok if fused is None else bool(fused)
    if telemetry:
        # host constants of the exact codec cost model, baked into the
        # traced wire_bytes column
        t_bytes_wire, t_bytes_full = tmetrics.wire_bytes_model(
            spec, wire_dtype)

    def one(p, b, r):
        (l, aux), g = jax.value_and_grad(loss_fn, has_aux=True)(p, b, r)
        return g, l

    def local_grads(pan, batch, rngs):
        """(grad panel, (m,) losses) of every agent's local step."""
        if spec.sharded:
            # agent rows live on different devices: one vmapped program
            params = panel_mod.from_panel(pan, spec,
                                          leaf_shardings=param_shardings)
            with scope("dsgd.fwd_bwd"):
                grads, losses = jax.vmap(one)(params, batch, rngs)
            return panel_mod.to_panel(grads, spec), losses

        # one device holds every row: agents run one after another. A
        # vmap over the (m, D) panel fuses the panel<->leaf relayouts into
        # the forward/backward, and the TPU compiler's code then grows
        # with D: olmo-1b at 1 layer, m=4 compiled to 735 MB of code in
        # 776 s with 30 GB of host memory, against 30 MB in 16 s this way.
        # The relayout is whole-panel and outside the loop: a panel tile
        # holds 128 columns of every agent, so one agent's row touches a
        # sliver of every tile, while agent-stacked slabs slice whole tiles
        return _agents_in_turn(one, pan, batch, rngs, spec)

    def segment(state, batches, Ws, rng, active=None, global_rounds=None,
                live=None):
        m = next(iter(state["panel"].values())).shape[0]
        S = Ws.shape[0]
        if needs_ef and "wire_err" not in state:
            raise ValueError(
                "spec's wire policy uses error feedback but the state has "
                "no 'wire_err' residual panel; build the state with "
                "init_panel_state(..., wire=...)")
        if needs_stats and "merge_stat" not in state:
            raise ValueError(
                f"spec's merge operator '{merger.name}' maintains "
                "statistics panels but the state has no 'merge_stat'; "
                "build the state with init_panel_state(..., merger=...)")

        def row_mask(mask, a):
            """(m,) bool mask broadcast against a leading-(m,) leaf."""
            return mask.reshape((m,) + (1,) * (a.ndim - 1))

        def err_dec(e):
            # EF residual storage: decode ONLY inside the communicating
            # branches — idle rounds never touch the stored bits
            if not res_err or e is None:
                return e
            return _res_read(e, res_err, use_pallas=res_pallas)

        def err_enc(ne, ekey, eold, W):
            # re-encode the post-mix residual; idle ROWS of W (unmatched
            # agents — their residual value is untouched by the mix)
            # keep their OLD stored bits instead of re-quantizing the
            # decoded value: strictly better precision, and it preserves
            # the per-row idle rule bit-exactly through storage
            if not res_err or ne is None:
                return ne
            enc = _res_write(ne, res_err, ekey, spec,
                             use_pallas=res_pallas)
            if eold is not None:
                ir = jnp.all(W == jnp.eye(m, dtype=W.dtype), axis=1)
                enc = {k: (jax.tree.map(
                    lambda a, b: jnp.where(row_mask(ir, a), b, a),
                    v, eold[k]) if k in res_err else v)
                    for k, v in enc.items()}
            return enc

        def agent_mets(out_pan, la, ga, lv, alive, W, full_bw):
            # the per-agent metric panel: pure reads of arrays the round
            # already materialized (la/ga are (H, m) stacks from the
            # local scan; out_pan is the post-mix panel)
            return {
                "loss_agent": jnp.mean(la, axis=0),
                "grad_norm_agent": jnp.mean(ga, axis=0),
                "dist_to_mean": tmetrics.agent_dist_to_mean(
                    out_pan, live=alive),
                "live": tmetrics.live_trits(lv, m),
                "wire_bytes": tmetrics.round_wire_bytes(
                    W, bytes_wire=t_bytes_wire, bytes_full=t_bytes_full,
                    full_bandwidth=full_bw, lv=lv),
            }

        def make_local_body(alive):
            # alive=None compiles the exact pre-liveness body; a (m,)
            # bool mask keeps non-live rows' params/moments/stats frozen
            # while consuming the SAME rng stream (survivor draws match
            # the fault-free twin)
            if alive is not None:
                lf = alive.astype(jnp.float32)
                n_live = jnp.maximum(jnp.sum(lf), 1.0)

                def freeze(new, old):
                    return jax.tree.map(
                        lambda a, b: jnp.where(row_mask(alive, a), a, b),
                        new, old)

            def local_body(carry, xs):
                pan, opt, mstat = carry
                batch, r = xs
                rngs = jax.random.split(r, m)
                with scope("dsgd.local_grad"):
                    gpan, losses = local_grads(pan, batch, rngs)
                if not plain_merge and merger.local_stat:
                    upd = merger.update_local(mstat, gpan)
                    mstat = upd if alive is None else freeze(upd, mstat)
                with scope("dsgd.local_update"):
                    if not res_mom:
                        new_pan, new_opt = jax.vmap(optimizer.update)(
                            gpan, opt, pan)
                    elif res_fused:
                        # single-sweep fused kernel: no f32 moment view
                        # ever hits HBM; same SR key folds as the
                        # unfused branch below, so trajectories match
                        new_pan, new_opt = _fused_opt_update(
                            gpan, opt, pan, optimizer, res_mom, spec,
                            _res_key(r, "moments", res_mom_key),
                            use_pallas=res_pallas)
                    else:
                        # moment storage fusion: decode -> update ->
                        # re-encode inside the SAME donated step (the f32
                        # view is a transient XLA temporary, never a
                        # carried buffer); the SR key folds off the
                        # LOCAL-STEP rng so every step draws fresh bits
                        opt_f = _opt_read(opt, res_mom, mom_keys,
                                          use_pallas=res_pallas)
                        new_pan, new_opt = jax.vmap(optimizer.update)(
                            gpan, opt_f, pan)
                        new_opt = _opt_write(
                            new_opt, res_mom, mom_keys,
                            _res_key(r, "moments", res_mom_key), spec,
                            use_pallas=res_pallas)
                if alive is None:
                    loss = jnp.mean(losses)
                    gn = panel_mod.panel_norm(gpan, axis_mean=True)
                else:
                    new_pan = freeze(new_pan, pan)
                    new_opt = freeze(new_opt, opt)
                    loss = jnp.sum(lf * losses) / n_live
                    gn = panel_mod.panel_norm(gpan, axis_mean=True,
                                              rows=lf / n_live)
                ys = (loss, gn)
                if telemetry:
                    ys = ys + (tmetrics.agent_loss(losses, alive),
                               tmetrics.agent_grad_norm(gpan, alive))
                return (new_pan, new_opt, mstat), ys

            return local_body

        def _live_comm(pan, opt, werr, mstat, W, wkey, ekey, lv, alive,
                       glob, losses, gns, la=None, ga=None):
            # elastic round: mix over the (already degraded) W, then
            # apply the liveness mask — DEAD rows pass through, RESYNC
            # rows pull the live agents' post-mix mean and restart their
            # carried state from it
            sync = lv == 2
            not_live = ~alive
            kw = dict(wire_dtype=wire_dtype, use_pallas=use_pallas,
                      spec=spec, key=wkey)
            idle = jnp.all(W == jnp.eye(m, dtype=W.dtype))
            is_full = (None if plain_merge else
                       (glob if glob is not None else
                        jnp.all(W == jnp.full((m, m), 1.0 / m, W.dtype))))

            def comm(args):
                # monitor's folded-mean matmul (an extra 1^T/m row on W)
                # mirrors the live=None path bit-for-bit: an all-live
                # mask must not perturb the numerics. The folded mean
                # itself is unused — the live-only Xi is computed below
                p, e = args
                if monitor:
                    mixed, _, ne = panel_mod.mix_dense_mean(
                        p, W, err=err_dec(e), **kw)
                    return mixed, err_enc(ne, ekey, e, W)
                if needs_ef:
                    mixed, ne = panel_mod.mix_dense(p, W, err=err_dec(e),
                                                    **kw)
                    return mixed, err_enc(ne, ekey, e, W)
                return panel_mod.mix_dense(p, W, **kw), e

            def gossip_fn(args):
                return jax.lax.cond(idle, lambda a: a, comm, args)

            def merge_fn(args):
                p, e = args
                mixed, _, ne = merging_mod.merge_panel(
                    p, merger, stats=mstat, spec=spec,
                    wire_dtype=wire_dtype, key=wkey, err=err_dec(e),
                    use_pallas=use_pallas,
                    live=alive)
                return mixed, err_enc(ne, ekey, None, None)

            werr_in = werr
            if plain_merge:
                mixed, werr_m = jax.lax.cond(idle, lambda a: a, comm,
                                             (pan, werr))
            else:
                mixed, werr_m = jax.lax.cond(is_full, merge_fn, gossip_fn,
                                             (pan, werr))

            lf = alive.astype(jnp.float32)
            lw = lf / jnp.maximum(jnp.sum(lf), 1.0)
            out_pan = {}
            for k, x in mixed.items():
                # dead AND resync agents did not participate in the mix:
                # their rows are identity rows of the degraded W
                # (defense in depth — the per-row idle rule already
                # restores them under a lossy codec)
                y = jnp.where(row_mask(not_live, x), pan[k], x)
                mu = jnp.tensordot(lw, y.astype(jnp.float32), axes=1,
                                   precision="highest")
                y = jnp.where(row_mask(sync, y), mu[None].astype(y.dtype),
                              y)
                out_pan[k] = panel_mod._constrain_group(y, spec, k)
            # resync rows restart their carried state from the synced
            # params: zero moments, codec-fresh residual, fresh stats
            if not res_mom:
                opt = jax.tree.map(
                    lambda a: jnp.where(row_mask(sync, a),
                                        jnp.zeros_like(a), a), opt)
            else:
                # stored moments zero to the CANONICAL stored zero
                # (Storage.zero_like == init(zeros) bit-for-bit), so a
                # rejoined row matches a freshly initialised agent's
                def zero_rows(k, v):
                    if k in mom_keys:
                        zero = {g: (res_mom[g].zero_like(x)
                                    if g in res_mom else
                                    jax.tree.map(jnp.zeros_like, x))
                                for g, x in v.items()}
                    else:
                        zero = jax.tree.map(jnp.zeros_like, v)
                    return jax.tree.map(
                        lambda a, z: jnp.where(row_mask(sync, a), z, a),
                        v, zero)

                opt = {k: zero_rows(k, v) for k, v in opt.items()}
            if werr_m is not None:
                new_werr = {}
                for k, e in werr_m.items():
                    if res_err and k in res_err:
                        # stored residual: dead rows take their OLD
                        # stored bits leafwise (q AND scale — the PR 6
                        # bit-exact passthrough through storage), resync
                        # rows a deterministic re-encode of the fresh
                        # codec state
                        e = jax.tree.map(
                            lambda a, b: jnp.where(
                                row_mask(not_live, a), b, a),
                            e, werr_in[k])
                        fresh = res_err[k].init(
                            wire_mod.get_codec(spec.wire_of(k)).init_err(
                                out_pan[k]).astype(jnp.float32))
                        e = jax.tree.map(
                            lambda a, b: jnp.where(row_mask(sync, a), b,
                                                   a), e, fresh)
                        new_werr[k] = _res_constrain(e, spec, k)
                    else:
                        e = jnp.where(row_mask(not_live, e), werr_in[k],
                                      e)
                        fresh = wire_mod.get_codec(
                            spec.wire_of(k)).init_err(
                                out_pan[k]).astype(e.dtype)
                        new_werr[k] = panel_mod._constrain_group(
                            jnp.where(row_mask(sync, e), fresh, e),
                            spec, k)
                werr_m = new_werr
            if mstat is not None:
                fresh = merger.init_stats(out_pan)
                mstat = {
                    name: {k: panel_mod._constrain_group(
                        jnp.where(row_mask(sync, v), fresh[name][k], v),
                        spec, k) for k, v in grp.items()}
                    for name, grp in mstat.items()}
            mets = {"loss": jnp.mean(losses), "grad_norm": jnp.mean(gns),
                    "grad_norm_max": jnp.max(gns)}
            if monitor:
                mets["consensus"] = panel_mod.consensus_distance(
                    out_pan, use_pallas=use_pallas,
                    spec=spec, live=alive)
            if telemetry:
                mets.update(agent_mets(
                    out_pan, la, ga, lv, alive, W,
                    is_full if has_delta else None))
            return (out_pan, opt, werr_m, mstat), mets

        def round_core(carry, W, batch_r, r, glob, lv):
            pan, opt, werr, mstat = carry
            alive = None if lv is None else lv == 1
            rs = jax.random.split(r, local_steps)
            (pan, opt, mstat), step_ys = jax.lax.scan(
                make_local_body(alive), (pan, opt, mstat), (batch_r, rs))
            if telemetry:
                losses, gns, la, ga = step_ys
            else:
                (losses, gns), la, ga = step_ys, None, None
            if not plain_merge and merger.round_stat:
                upd = merger.update_round(mstat, pan)
                if alive is not None:
                    upd = jax.tree.map(
                        lambda a, b: jnp.where(row_mask(alive, a), a, b),
                        upd, mstat)
                mstat = upd
            wkey = _wire_key(r, needs_key)
            ekey = _res_key(r, "wire_err", res_err_key)
            if lv is not None:
                return _live_comm(pan, opt, werr, mstat, W, wkey, ekey,
                                  lv, alive, glob, losses, gns, la, ga)
            # W == I rounds communicate nothing: skip the matmul AND the
            # codec (no payload travels, so nothing may be quantized and
            # the error-feedback residual must pass through untouched)
            idle = jnp.all(W == jnp.eye(m, dtype=W.dtype))
            # non-uniform operators take over the GLOBAL rounds: the
            # explicit per-round mask when given, else the W fingerprint
            # (the 1/m matrix the schedulers emit for global merging —
            # see the docstring caveat); after the broadcast every row
            # is identical, so Xi == 0
            is_full = (None if plain_merge else
                       (glob if glob is not None else
                        jnp.all(W == jnp.full((m, m), 1.0 / m, W.dtype))))
            kw = dict(wire_dtype=wire_dtype, use_pallas=use_pallas,
                      spec=spec, key=wkey)

            if monitor:
                def comm(args):
                    p, e = args
                    mixed, mean, ne = panel_mod.mix_dense_mean(
                        p, W, err=err_dec(e), **kw)
                    return (mixed, err_enc(ne, ekey, e, W),
                            panel_mod.consensus_from_mean(mixed, mean))

                def idle_fn(args):
                    p, e = args
                    return p, e, panel_mod.consensus_distance(
                        p, use_pallas=use_pallas,
                        spec=spec)

                def gossip_fn(args):
                    return jax.lax.cond(idle, idle_fn, comm, args)

                def merge_fn(args):
                    p, e = args
                    mixed, _, ne = merging_mod.merge_panel(
                        p, merger, stats=mstat, spec=spec,
                        wire_dtype=wire_dtype, key=wkey, err=err_dec(e),
                        use_pallas=use_pallas)
                    return (mixed, err_enc(ne, ekey, None, None),
                            jnp.zeros((), jnp.float32))

                if plain_merge:
                    mixed, werr, xi = jax.lax.cond(
                        idle, idle_fn, comm, (pan, werr))
                else:
                    mixed, werr, xi = jax.lax.cond(
                        is_full, merge_fn, gossip_fn, (pan, werr))
                mets = {"loss": jnp.mean(losses),
                        "grad_norm": jnp.mean(gns),
                        "grad_norm_max": jnp.max(gns), "consensus": xi}
            else:
                def comm(args):
                    p, e = args
                    if needs_ef:
                        mixed, ne = panel_mod.mix_dense(
                            p, W, err=err_dec(e), **kw)
                        return mixed, err_enc(ne, ekey, e, W)
                    return panel_mod.mix_dense(p, W, **kw), e

                def gossip_fn(args):
                    return jax.lax.cond(idle, lambda a: a, comm, args)

                def merge_fn(args):
                    p, e = args
                    mixed, _, ne = merging_mod.merge_panel(
                        p, merger, stats=mstat, spec=spec,
                        wire_dtype=wire_dtype, key=wkey, err=err_dec(e),
                        use_pallas=use_pallas)
                    return mixed, err_enc(ne, ekey, None, None)

                if plain_merge:
                    mixed, werr = jax.lax.cond(
                        idle, lambda a: a, comm, (pan, werr))
                else:
                    mixed, werr = jax.lax.cond(
                        is_full, merge_fn, gossip_fn, (pan, werr))
                mets = {"loss": jnp.mean(losses),
                        "grad_norm": jnp.mean(gns),
                        "grad_norm_max": jnp.max(gns)}
            if telemetry:
                mets.update(agent_mets(
                    mixed, la, ga, lv, alive, W,
                    is_full if has_delta else None))
            return (mixed, opt, werr, mstat), mets

        def run_round(carry, W, batch_r, r, glob, lv):
            if not res_stat or carry[3] is None:
                return round_core(carry, W, batch_r, r, glob, lv)
            # stat-panel storage: ONE decode to the f32 compute view at
            # round entry, one encode at round exit — every operator the
            # round runs (update_local/update_round/merge_panel) sees
            # f32. DEAD rows keep their stored bits verbatim (q AND
            # scale); RESYNC rows encode deterministically so a rejoin
            # bit-matches a fresh init of the synced params.
            pan, opt, werr, mstat = carry
            mstat_f = {name: _res_read(grp, res_stat,
                                       use_pallas=res_pallas)
                       for name, grp in mstat.items()}
            (pan, opt, werr, mstat_f), mets = round_core(
                (pan, opt, werr, mstat_f), W, batch_r, r, glob, lv)
            skey = _res_key(r, "stats", res_stat_key)
            sync = None if lv is None else lv == 2
            dead = None if lv is None else lv == 0
            new_mstat = {}
            for i, name in enumerate(sorted(mstat_f)):
                ki = None if skey is None else jax.random.fold_in(skey, i)
                enc = _res_write(mstat_f[name], res_stat, ki, None,
                                 use_pallas=res_pallas)
                if lv is not None:
                    det = _res_init(mstat_f[name], res_stat)
                    old = mstat[name]
                    enc = {g: jax.tree.map(
                        lambda a, d_, o_: jnp.where(
                            row_mask(dead, a), o_,
                            jnp.where(row_mask(sync, a), d_, a)),
                        v, det[g], old[g]) for g, v in enc.items()}
                new_mstat[name] = {g: _res_constrain(v, spec, g)
                                   for g, v in enc.items()}
            return (pan, opt, werr, new_mstat), mets

        def round_body(carry, xs):
            W, batch_r, r = xs[:3]
            rest = list(xs[3:])
            glob = rest.pop(0) if global_rounds is not None else None
            lv = rest.pop(0) if live is not None else None
            act = rest.pop(0) if active is not None else None
            if act is None:
                return run_round(carry, W, batch_r, r, glob, lv)

            def inactive(c):
                # zeros matching run_round's metric schema exactly
                mets_sds = jax.eval_shape(
                    lambda cc: run_round(cc, W, batch_r, r, glob, lv)[1],
                    c)
                return c, jax.tree.map(
                    lambda s: jnp.zeros(s.shape, s.dtype), mets_sds)

            return jax.lax.cond(
                act, lambda c: run_round(c, W, batch_r, r, glob, lv),
                inactive, carry)

        rngs = jax.random.split(rng, S)
        xs = (Ws, batches, rngs)
        if global_rounds is not None:
            xs = xs + (global_rounds,)
        if live is not None:
            xs = xs + (live,)
        if active is not None:
            xs = xs + (active,)
        werr0 = state.get("wire_err") if needs_ef else None
        mstat0 = state.get("merge_stat") if needs_stats else None
        (pan, opt, werr, mstat), metrics = jax.lax.scan(
            round_body, (state["panel"], state["opt"], werr0, mstat0), xs)
        steps = (S if active is None
                 else jnp.sum(active.astype(jnp.int32))) * local_steps
        out = {"panel": pan, "opt": opt, "step": state["step"] + steps}
        if werr is not None:
            out["wire_err"] = werr
        if mstat is not None:
            out["merge_stat"] = mstat
        return out, metrics

    jit_kw = {} if in_shardings is None else {"in_shardings": in_shardings}
    return jax.jit(segment, donate_argnums=(0,) if donate else (), **jit_kw)


def make_parallel_step(loss_fn: Callable, optimizer: Optimizer):
    """Parallel SGD / FedAvg(H=1) baseline: one shared model; gradients are
    averaged over the m per-agent batches every step (the paper's reference
    rate O(sigma^2/(m eps^2) + 1/eps))."""

    def step(state, batch, rng):
        m = jax.tree.leaves(batch)[0].shape[0]
        rngs = jax.random.split(rng, m)

        def one(b, r):
            (l, aux), g = jax.value_and_grad(
                loss_fn, has_aux=True)(state["params"], b, r)
            return g, l

        grads, losses = jax.vmap(one)(batch, rngs)
        gbar = jax.tree.map(lambda g: jnp.mean(g, axis=0), grads)
        new_p, new_opt = optimizer.update(gbar, state["opt"], state["params"])
        return {"params": new_p, "opt": new_opt,
                "step": state["step"] + 1}, {"loss": jnp.mean(losses)}

    return step


def init_parallel_state(init_params: Callable, optimizer: Optimizer, rng):
    p = init_params(rng)
    return {"params": p, "opt": optimizer.init(p),
            "step": jnp.zeros((), jnp.int32)}
