"""Training cells: decentralized training of m agents, through the segment
function that ``repro.core.dsgd.make_panel_segment`` builds, fed as
``repro.launch.train`` feeds it (``init_panel_state``, ``make_schedule``,
stacked mixing matrices and global-round flags).

Placement follows from the cell (:func:`placement`): on one chip every
agent runs on one device; with as many chips as agents, one agent per
chip, the panel's rows over the host mesh (``repro.launch.mesh.
make_host_mesh``), as ``repro.launch.train --mesh host`` runs it. The
reference follows the same placement.

Set-up makes the weights and a pool of distinct token segments on the
device from the seed, builds the state, and drives the first
``check_calls`` segments through the window's own call: they compile it,
and they are what the reference follows. The window then runs whole
segments back to back until ``--seconds`` have passed, the jobs of the
schedule cycling so that every window holds global rounds. A traced run
hands its readers, besides the trace's reduction, the segment's own
metrics of every call of the window (``ctx["program"]``), fetched once
the window has closed.
"""
from __future__ import annotations

import gc
import time

import numpy as np

from bench import common, gen, program
from bench import reference as ref


MOMENT_DTYPES = {"bf16": "bfloat16", "f32": "float32"}
# the reference's forward and backward take an agent's batch this many
# sequences at a time: the one-chip cells' batch of 4 whole; a batch of 16 x
# 512 in four blocks keeps its float32 activations within a chip
REFERENCE_ROWS = 4


def placement(chips, agents):
    """None when every agent runs on one chip; the host mesh, one agent per
    chip, when there are as many chips as agents. Any other pair is an
    error."""
    if chips == 1:
        return None
    if chips == agents:
        from repro.launch.mesh import make_host_mesh
        return make_host_mesh(chips)
    raise ValueError(f"{agents} agents on {chips} chips: a training cell "
                     f"runs on one chip or on one chip per agent")


def agent_rows(mesh, lead=0):
    """The sharding of an array whose axis ``lead`` is the agent axis: one
    agent per chip of ``mesh``."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    return NamedSharding(mesh, P(*([None] * lead), ("pod", "agent")))


def round_fault(W, is_global, t, every):
    """Whether round ``t``'s mixing matrix or global flag, as the program's
    schedule made them, departs from the form the traffic states: a
    global merge, ``W`` = 1/m exactly, at every round t with (t + 1) %
    ``every`` == 0, and at every other round a partial matching, each
    agent either keeping its row or averaging 50/50 with one peer."""
    m = W.shape[0]
    if bool(is_global) != ((t + 1) % every == 0):
        return True
    if is_global:
        return not np.array_equal(W, np.full((m, m), 1 / m, W.dtype))
    if not np.array_equal(W, W.T):
        return True
    for k in range(m):
        others = np.delete(W[k], k)
        kept = W[k, k] == 1 and not others.any()
        paired = (W[k, k] == 0.5 and np.count_nonzero(others) == 1
                  and others.sum() == 0.5)
        if not (kept or paired):
            return True
    return False


def job_segments(traffic, m, seed, n_jobs):
    """(segments, faults): mixing matrices and global-round flags, one
    (S, m, m) / (S,) pair per segment, of ``n_jobs`` jobs of the schedule,
    each with its own seed, cut into segments, starting at segment
    ``start_segment`` of the first job; and how many of those jobs'
    rounds depart from the traffic's form (:func:`round_fault`)."""
    from repro.core.schedule import make_schedule
    S = traffic["rounds_per_segment"]
    rounds = traffic["job_rounds"]
    segs, faults = [], 0
    for j in range(n_jobs):
        sched = make_schedule(traffic["schedule"], m, rounds,
                              seed=int(seed) * 1000 + j,
                              **traffic.get("schedule_args", {}))
        Ws, glob = [], []
        for t in range(rounds):
            Ws.append(np.asarray(sched.mixing_matrix(t, {}), np.float32))
            glob.append(sched.last_kind == "global")
            faults += round_fault(Ws[-1], glob[-1], t,
                                  traffic["global_every"])
        for s in range(0, rounds, S):
            segs.append((np.stack(Ws[s:s + S]), np.asarray(glob[s:s + S])))
    return segs[traffic.get("start_segment", 0):], faults


class Trainer:
    """The program's training path for one cell, at the cell's sizes."""

    def __init__(self, jax, cfg, traffic, seed, *, loss_wrap=None,
                 chips=1):
        import jax.numpy as jnp
        from repro.core import dsgd
        from repro.models import build_model
        from repro.optim import make_optimizer
        self.jax, self.jnp = jax, jnp
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        job = cfg["job"]
        self.m, self.b, self.seq = job["agents"], job["batch"], job["seq_len"]
        self.S = traffic["rounds_per_segment"]
        self.H = traffic["local_steps"]
        self.mesh = placement(chips, self.m)
        self.model = build_model(program.model_config(cfg))
        self.opt = make_optimizer("adamw", job["lr"],
                                  weight_decay=job["weight_decay"])
        self.make_params = jax.jit(lambda k: ref.make_params(cfg, k))
        self.k_w = common.seed_key(jax, seed, 1)
        params = self.make_params(self.k_w)
        program.check_layout(jax, cfg, self.model, params)
        tree = program.to_program(cfg, params)
        del params
        key = common.seed_key(jax, seed, 2)

        def init(t, k):
            return dsgd.init_panel_state(
                lambda _: t, self.opt, self.m, k, same_init=True,
                residency=f"moments={job['moments']}", mesh=self.mesh)
        if self.mesh is None:
            self.state, self.spec = init(tree, key)
        else:
            # built in one program, each chip making its own agent's rows:
            # run eagerly, the stacked panel and moments of every agent
            # would first lie whole on the first chip
            box = {}

            def build(t, k):
                state, box["spec"] = init(t, k)
                return state
            shapes = jax.eval_shape(build, tree, key)
            self.spec = box["spec"]
            self.state = jax.jit(build, out_shardings=(
                dsgd.panel_state_shardings(shapes, self.spec)))(tree, key)
        del tree
        loss_fn = self.model.loss_fn
        if loss_wrap is not None:
            loss_fn = loss_wrap(loss_fn)
        self.segment = dsgd.make_panel_segment(loss_fn, self.opt, self.H,
                                               self.spec)
        # traffic: distinct token segments, resident on the device
        P = traffic["pool_segments"]
        pool = jax.jit(lambda k: gen.token_pool(
            jax, k, shape=(P, self.S, self.H, self.m, self.b, self.seq),
            vocab=cfg["vocab_size"], alpha=traffic["alpha"],
            domains=traffic["domains"]))(common.seed_key(jax, seed, 3))
        ones = jnp.ones((P, self.S, self.H, self.m, self.b, self.seq),
                        jnp.float32)
        self.pool = [{"tokens": pool["tokens"][i],
                      "targets": pool["targets"][i], "mask": ones[i]}
                     for i in range(P)]
        if self.mesh is not None:
            # (S, H, m, b, seq): each agent's batches on its own chip
            rows = agent_rows(self.mesh, lead=2)
            self.pool = [{k: jax.device_put(v, rows) for k, v in b.items()}
                         for b in self.pool]
        del pool, ones
        n_jobs = traffic.get("jobs", 4)
        segs, self.schedule_faults = job_segments(traffic, self.m, seed,
                                                  n_jobs)
        self.Ws = [jnp.asarray(w) for w, _ in segs]
        self.Ws_host = [w for w, _ in segs]
        self.glob = [jnp.asarray(g) for _, g in segs]
        k_seg = common.seed_key(jax, seed, 4)
        self.keys = [jax.random.fold_in(k_seg, i) for i in range(len(segs))]
        self.active = jnp.ones((self.S,), bool)
        self.calls = 0
        self.layout = None
        if self.mesh is not None:
            # one program, compiled (or loaded from the cache) once, for the
            # state as built. It returns each agent's step counter on its
            # own chip, where the build replicates them: each call puts its
            # state back in the layout the program takes
            self.segment = self.segment.lower(*self.args(0)).compile()
            self.layout = self.segment.input_shardings[0][0]

    @property
    def tokens_per_call(self):
        return self.S * self.H * self.m * self.b * self.seq

    def args(self, i):
        """The segment's arguments for call ``i``."""
        w = i % len(self.Ws)
        return (self.state, self.pool[i % len(self.pool)], self.Ws[w],
                self.keys[w], self.active, self.glob[w], None)

    def call(self):
        """Dispatch the next segment; returns its metrics (not waited on)."""
        self.state, mets = self.segment(*self.args(self.calls))
        if self.layout is not None:
            self.state = self.jax.device_put(self.state, self.layout)
        self.calls += 1
        return mets

    def compiled_text(self):
        """The HLO text of the program the window runs."""
        seg = self.segment
        if not hasattr(seg, "as_text"):
            seg = seg.lower(*self.args(0)).compile()
        return seg.as_text()

    def deltas(self):
        """(m, leaves) norms of each agent's change of each weight since the
        start, read from the program's state."""
        jax, jnp = self.jax, self.jnp
        from repro.core import panel as panel_mod
        spec = self.spec

        def run(pan, p0):
            tree = panel_mod.from_panel(pan, spec)
            p = program.from_program(self.cfg, tree)
            return jnp.stack([
                jnp.sqrt(jnp.sum(jnp.square(p[k] - p0[k][None]),
                                 axis=tuple(range(1, p[k].ndim))))
                for k in sorted(p0)], axis=1)
        p0 = self.make_params(self.k_w)
        out = np.asarray(jax.jit(run)(self.state["panel"], p0))
        del p0
        return out

    def free(self):
        self.state = self.pool = None
        gc.collect()


def reference_run(jax, cfg, traffic, trainer_inputs, calls, dtype,
                  mesh=None):
    """The reference over the first ``calls`` segments: per round its mean
    loss, the norm of the agent-mean gradient (mean over the local
    steps) and Xi after the mix; per agent and weight the norm of the
    change; per weight the norm of the first gradient. With ``mesh`` the
    agent-stacked parameters, moments and gradients lie one agent per
    chip, and each chip runs the agent loop over its own agents."""
    jnp = jax.numpy
    job = cfg["job"]
    m = job["agents"]
    k_w, pool, Ws_host = trainer_inputs
    mom = MOMENT_DTYPES[job["moments"]]
    hp = dict(lr=job["lr"], b1=0.9, b2=0.999, eps=1e-8,
              wd=job["weight_decay"], moment_dtype=mom)
    with jax.default_matmul_precision("highest"):
        make = jax.jit(lambda k: ref.make_params(cfg, k))
        adam = jax.jit(lambda p, g, m_, v_, c: ref.adamw(p, g, m_, v_, c,
                                                         **hp),
                       donate_argnums=(0, 2, 3))
        if mesh is None:
            grads_fn = jax.jit(ref.agent_grads(cfg, dtype, REFERENCE_ROWS))
            mix = jax.jit(ref.mix, donate_argnums=(1,))
        else:
            from jax.sharding import PartitionSpec as P
            rows = agent_rows(mesh)
            grads_fn = jax.jit(jax.shard_map(
                ref.agent_grads(cfg, dtype, REFERENCE_ROWS), mesh=mesh,
                in_specs=P(("pod", "agent")), out_specs=P(("pod", "agent")),
                check_vma=False))
            mix = jax.jit(ref.mix, donate_argnums=(1,), out_shardings=rows)
        xi = jax.jit(ref.consensus)
        gnorm = jax.jit(lambda g: ref.tree_norm(
            jax.tree.map(lambda x: jnp.mean(x, 0), g)))
        leaf_norms = jax.jit(lambda g: jnp.stack(
            [jnp.sqrt(jnp.mean(jnp.sum(jnp.square(g[k]),
                                       axis=tuple(range(1, g[k].ndim)))))
             for k in sorted(g)]))
        p0 = make(k_w)
        if mesh is None:
            theta = jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (m,) + x.shape), p0)
            mm = jax.tree.map(lambda x: jnp.zeros(x.shape, mom), theta)
            vv = jax.tree.map(lambda x: jnp.zeros(x.shape, mom), theta)
        else:
            theta = jax.jit(lambda p: jax.tree.map(
                lambda x: jnp.broadcast_to(x[None], (m,) + x.shape), p),
                out_shardings=rows)(p0)
            zeros = jax.jit(lambda t: jax.tree.map(
                lambda x: jnp.zeros(x.shape, mom), t), out_shardings=rows)
            mm, vv = zeros(theta), zeros(theta)
        count = jnp.zeros((), jnp.int32)
        loss_r, gn_r, xi_r, first_g = [], [], [], None
        for c in range(calls):
            batch = pool[c]
            for r in range(traffic["rounds_per_segment"]):
                ls, gs = [], []
                for h in range(traffic["local_steps"]):
                    lval, g = grads_fn(theta, batch["tokens"][r, h],
                                       batch["targets"][r, h])
                    if first_g is None:
                        first_g = np.asarray(leaf_norms(g))
                    ls.append(float(jnp.mean(lval)))
                    gs.append(float(gnorm(g)))
                    count = count + 1
                    theta, mm, vv = adam(theta, g, mm, vv, count)
                    del g
                W = Ws_host[c][r]
                if not np.array_equal(W, np.eye(m)):
                    theta = mix(jnp.asarray(W), theta)
                loss_r.append(float(np.mean(ls)))
                gn_r.append(float(np.mean(gs)))
                xi_r.append(float(xi(theta)))
        dn = jax.jit(lambda t, p: jnp.stack([
            jnp.sqrt(jnp.sum(jnp.square(t[k] - p[k][None]),
                             axis=tuple(range(1, t[k].ndim))))
            for k in sorted(p)], axis=1))(theta, p0)
        deltas = np.asarray(dn)
    return {"loss": np.asarray(loss_r), "grad_norm": np.asarray(gn_r),
            "consensus": np.asarray(xi_r), "deltas": deltas,
            "first_grad": first_g}


def compare(prog, refr):
    """The numbers compared, each a relative gap:

    - ``loss``: the widest gap of a round's mean loss;
    - ``loss_first``: the gap of the first round's mean loss, before
      Adam has turned rounding into steps that differ;
    - ``grad_norm``: the widest gap of a round's agent-mean gradient norm;
    - ``grad_norm_first``: that gap in the first round, the gradients the
      optimizer got before Adam has turned rounding into steps that
      differ;
    - ``update``: the worst agent and weight, the gap between the norms
      of its change, over the larger of the reference's norm of that
      change and the median weight's. Weights whose first gradient is
      under a thousandth of the median weight's are left out;
    - ``consensus``: the widest gap of a round's Xi over the largest Xi
      of the reference's rounds."""
    rl, rg, rx = refr["loss"], refr["grad_norm"], refr["consensus"]
    gap = np.abs(prog["loss"] - rl) / np.abs(rl)
    loss, loss_first = float(np.max(gap)), float(gap[0])
    ggap = np.abs(prog["grad_norm"] - rg) / np.abs(rg)
    grad, grad_first = float(np.max(ggap)), float(ggap[0])
    fg = refr["first_grad"]
    keep = fg >= 1e-3 * np.median(fg)
    rd, pd = refr["deltas"][:, keep], prog["deltas"][:, keep]
    scale = np.maximum(rd, np.median(rd, axis=1, keepdims=True))
    update = float(np.max(np.abs(pd - rd) / scale))
    xscale = float(np.max(rx))
    cons = (float(np.max(np.abs(prog["consensus"] - rx)) / xscale)
            if xscale > 0 else float(np.max(np.abs(prog["consensus"]))))
    return {"loss": loss, "loss_first": loss_first, "grad_norm": grad,
            "grad_norm_first": grad_first, "update": update, "consensus": cons,
            "schedule_faults": prog.get("schedule_faults", 0)}


def setup_and_check_calls(jax, cfg, traffic, seed, loss_wrap=None,
                          chips=1):
    """Build the trainer and drive the check calls. Returns (trainer,
    program readings of those calls)."""
    tr = Trainer(jax, cfg, traffic, seed, loss_wrap=loss_wrap, chips=chips)
    K = traffic["check_calls"]
    mets = [jax.device_get(tr.call()) for _ in range(K)]
    prog = {k: np.concatenate([np.asarray(x[k]) for x in mets])
            for k in ("loss", "grad_norm", "consensus")}
    prog["deltas"] = tr.deltas()
    prog["schedule_faults"] = tr.schedule_faults
    return tr, prog


def program_counters(mets):
    """The segment's metrics of every call, fetched to the host once and
    concatenated per key (the per-round arrays of successive calls, end
    to end)."""
    import jax
    return jax.tree.map(
        lambda *xs: np.concatenate([np.atleast_1d(x) for x in xs]),
        *jax.device_get(mets))


def run(jax, cell, cfg, traffic, limits, *, seed, seconds, trace, t_start,
        devs, peaks, per_layer, declared):
    tr, prog = setup_and_check_calls(jax, cfg, traffic, seed,
                                     chips=cell["chips"])
    common.print_memory_peaks(devs, "after the check calls")
    mesh = tr.mesh
    inputs = (tr.k_w, tr.pool[:traffic["check_calls"]],
              tr.Ws_host[:traffic["check_calls"]])
    setup_s = time.perf_counter() - t_start

    prof = None
    if trace:
        from bench import trace as tr_mod
        names = tr_mod.op_names(tr.compiled_text())
        prof = common.work_dir("trace", cell["name"])
        jax.profiler.start_trace(prof)
    watch = common.WindowWatch(jax)
    span = jax.profiler.TraceAnnotation("bench.window")
    span.__enter__()
    watch.__enter__()
    losses, calls = [], []
    t0 = time.perf_counter()
    n = 0
    pending = None
    while True:
        with jax.profiler.TraceAnnotation("bench.dispatch"):
            mets = tr.call()
        if trace:
            calls.append(mets)
        n += 1
        if pending is not None:
            with jax.profiler.TraceAnnotation("bench.wait"):
                losses.extend(np.asarray(jax.device_get(pending)))
        pending = mets["loss"]
        if time.perf_counter() - t0 >= seconds:
            break
    with jax.profiler.TraceAnnotation("bench.wait"):
        jax.block_until_ready(tr.state)
        losses.extend(np.asarray(jax.device_get(pending)))
    t1 = time.perf_counter()
    watch.__exit__(None, None, None)
    span.__exit__(None, None, None)
    print(watch.line(), flush=True)
    if trace:
        jax.profiler.stop_trace()
    common.print_memory_peaks(devs, "after the window")
    window = t1 - t0
    tokens = n * tr.tokens_per_call
    rounds, local_steps = n * tr.S, n * tr.S * tr.H
    device = common.device_info(devs)
    counters = program_counters(calls) if trace else None
    del calls
    tr.free()
    del tr
    gc.collect()

    refr = reference_run(jax, cfg, traffic, inputs, traffic["check_calls"],
                         jax.numpy.float32, mesh=mesh)
    nums = compare(prog, refr)
    nums["nonfinite_losses"] = int(np.sum(~np.isfinite(losses)))
    checks = [(k, nums[k], limits[k]) for k in limits]
    correct, table = common.judge(checks)
    result = {"correct": correct, "attempted": n, "failed": 0,
              "device": device}
    if not trace:
        result["metrics"] = {
            "train_tokens_per_s": {"value": tokens / window,
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        return result, table
    ex = tr_mod.extract(common.newest_trace(prof), names)
    red = tr_mod.reduce(ex, declared["SCOPES"])
    busy = [d["busy_ns"] for d in red["devices"].values()]
    result["device"]["busy_s"] = float(np.mean(busy)) / 1e9
    result["device"]["window_s"] = red["window_ns"] / 1e9
    result["breakdown"] = {"device_ops": red["device_ops"],
                           "idle_gaps": red["idle_gaps"]}
    ctx = {"cfg": cfg, "traffic": traffic, "peaks": peaks,
           "chips": len(devs), "reduced": red, "program": counters,
           "counts": {"tokens": tokens, "calls": n, "rounds": rounds,
                      "local_steps": local_steps}}
    result["metrics"] = common.read_metrics(per_layer, ctx)
    common.clear_dir(prof)
    return result, table
