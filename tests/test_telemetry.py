"""Unified telemetry subsystem (repro/telemetry): per-agent metric
panels from the segment scan, the versioned deterministic event log +
wall-clock sidecar, latency histograms, and the serving engine's
snapshot/reset counters.

Key invariants pinned here:

* telemetry NEVER perturbs the trajectory — the segment's final panels
  are BIT-identical with the metric panels on or off;
* the per-agent columns decompose the scalar metrics exactly (loss is
  the mean of loss_agent, consensus is sqrt(mean(dist_to_mean^2)));
* wire bytes follow the engine's exact cost model — idle W rows pay 0,
  DEAD agents pay 0, RESYNC agents pay the full-precision pull;
* round metrics aggregate over ALL H local steps (mean + max) — the old
  driver reported only the LAST step's grad norm, hiding spikes;
* the deterministic event stream is byte-reproducible, schema-validated
  at emit time, and resume-safe via truncate-to-seq.
"""
import contextlib
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import dsgd, topology
from repro.optim import make_optimizer
from repro.telemetry import metrics as tmetrics
from repro.telemetry.events import (EventLog, make_run_id, read_events,
                                    validate_stream, wall_path)
from repro.telemetry.latency import (Histogram, default_bounds,
                                     histogram_set)

pytestmark = pytest.mark.telemetry


def _toy_problem(m=4, dim=12, classes=4):
    def init_params(rng):
        k1, _ = jax.random.split(rng)
        return {"w": jax.random.normal(k1, (dim, classes)) * 0.1,
                "b": jnp.zeros(classes)}

    def loss_fn(p, batch, rng=None):
        x, y = batch
        lg = x @ p["w"] + p["b"]
        nll = jnp.mean(jax.nn.logsumexp(lg, -1)
                       - jnp.take_along_axis(lg, y[:, None], -1)[:, 0])
        return nll, {}

    return init_params, loss_fn


def _segment_inputs(S, H, m, dim, classes, seed=0):
    rng = np.random.default_rng(seed)
    Ws = np.stack([topology.random_matching(m, 0.5, rng)
                   for _ in range(S)])
    bx = jnp.asarray(rng.normal(size=(S, H, m, 8, dim)).astype(np.float32))
    by = jnp.asarray(rng.integers(0, classes,
                                  size=(S, H, m, 8)).astype(np.int32))
    return jnp.asarray(Ws, jnp.float32), (bx, by)


# --------------------------------------------- round metric aggregation


def test_round_grad_norm_aggregates_all_local_steps():
    """Regression: make_dsgd_round reported gns[-1] — ONLY the final
    local step's grad norm — so a gradient spike at any earlier step was
    invisible. The metric is now the mean over all H steps plus an
    explicit max. A 50x input spike at LOCAL STEP 0 (of 3) must move
    both; under the old last-step metric the spiked run reported the
    same grad_norm as the clean one."""
    m, H, dim, classes = 4, 3, 12, 4
    init_params, loss_fn = _toy_problem(m, dim, classes)
    opt = make_optimizer("sgd", 1e-4)  # tiny lr: step-0 spike does not
    # meaningfully move the params, so the LAST step stays clean
    key = jax.random.PRNGKey(0)
    round_fn = dsgd.make_dsgd_round(loss_fn, opt, H)
    rng = np.random.default_rng(0)
    bx = jnp.asarray(rng.normal(size=(H, m, 8, dim)).astype(np.float32))
    by = jnp.asarray(rng.integers(0, classes, size=(H, m, 8)), jnp.int32)
    W = jnp.asarray(topology.ring(m), jnp.float32)

    state = dsgd.init_state(init_params, opt, m, key)
    _, base = round_fn(state, (bx, by), W, jax.random.PRNGKey(1))
    spiked = bx.at[0].multiply(50.0)  # spike ONLY local step 0
    state = dsgd.init_state(init_params, opt, m, key)
    _, spike = round_fn(state, (spiked, by), W, jax.random.PRNGKey(1))

    # the spike is visible in BOTH aggregates (the old gns[-1] metric
    # would have reported ~base["grad_norm"] for the spiked run)
    assert float(spike["grad_norm"]) > 5 * float(base["grad_norm"])
    assert float(spike["grad_norm_max"]) > 10 * float(
        base["grad_norm_max"])
    assert float(spike["grad_norm_max"]) > float(spike["grad_norm"])
    # clean run: max stays within the same order as the mean
    assert float(base["grad_norm_max"]) < 3 * float(base["grad_norm"])


# ------------------------------------------------ per-agent panel scan


def test_segment_per_agent_metrics_decompose_scalars():
    """telemetry=True adds five (S, m) columns to the segment's single
    device_get; they must decompose the scalar metrics exactly and
    follow the codec byte model (idle W rows pay 0)."""
    m, H, S, dim, classes = 4, 2, 4, 12, 4
    init_params, loss_fn = _toy_problem(m, dim, classes)
    opt = make_optimizer("adamw", 1e-2)
    state, spec = dsgd.init_panel_state(init_params, opt, m,
                                        jax.random.PRNGKey(0),
                                        wire="int8")
    seg = dsgd.make_panel_segment(loss_fn, opt, H, spec, telemetry=True)
    Ws, batches = _segment_inputs(S, H, m, dim, classes)
    _, mets = seg(state, batches, Ws, jax.random.PRNGKey(7))
    mets = jax.device_get(mets)

    for k in ("loss_agent", "grad_norm_agent", "dist_to_mean"):
        assert mets[k].shape == (S, m), k
    # scalar loss is the mean of the per-agent column
    np.testing.assert_allclose(np.mean(mets["loss_agent"], axis=1),
                               mets["loss"], rtol=1e-5)
    # consensus Xi decomposes as sqrt(mean(dist_to_mean^2))
    np.testing.assert_allclose(
        np.sqrt(np.mean(mets["dist_to_mean"] ** 2, axis=1)),
        mets["consensus"], rtol=1e-4)
    assert np.all(mets["grad_norm_agent"] > 0)
    # no fault plan: every agent LIVE every round
    np.testing.assert_array_equal(mets["live"], np.ones((S, m), np.int32))
    # exact codec cost model: idle (identity) rows of W pay 0 bytes,
    # communicating rows pay wire_total_bytes (int8 payload + scales)
    idle = np.all(np.asarray(Ws) == np.eye(m, dtype=np.float32), axis=2)
    expect = np.where(idle, 0, spec.wire_total_bytes)
    np.testing.assert_array_equal(mets["wire_bytes"], expect)


def test_segment_liveness_metrics_follow_trits():
    """DEAD rows report 0 loss and 0 wire bytes; RESYNC rows pay the
    full-precision pull; the live column is the trit mask verbatim."""
    m, H, S, dim, classes = 4, 2, 3, 12, 4
    init_params, loss_fn = _toy_problem(m, dim, classes)
    opt = make_optimizer("adamw", 1e-2)
    state, spec = dsgd.init_panel_state(init_params, opt, m,
                                        jax.random.PRNGKey(0),
                                        wire="int8")
    seg = dsgd.make_panel_segment(loss_fn, opt, H, spec, telemetry=True)
    _, batches = _segment_inputs(S, H, m, dim, classes)
    # degraded Ws: dead/resync agents hold identity rows (the schedule's
    # contract); agents 1,2 gossip every round, agent 3 idles
    W = np.eye(m, dtype=np.float32)
    W[1, 1] = W[2, 2] = 0.5
    W[1, 2] = W[2, 1] = 0.5
    Ws = jnp.asarray(np.stack([W] * S))
    live = jnp.asarray(np.array([[1, 1, 1, 1],
                                 [0, 1, 1, 1],    # agent 0 dead
                                 [2, 1, 1, 1]]),  # agent 0 resyncs
                       jnp.int32)
    active = jnp.ones((S,), bool)
    glob = jnp.zeros((S,), bool)
    _, mets = seg(state, batches, Ws, jax.random.PRNGKey(7), active,
                  glob, live)
    mets = jax.device_get(mets)

    np.testing.assert_array_equal(mets["live"], np.asarray(live))
    bytes_full = tmetrics.wire_bytes_model(spec)[1]
    wire = mets["wire_bytes"]
    # round 0 all-live: agent 0 idle (identity row) pays 0, the gossip
    # pair pays the codec bytes, idle agent 3 pays 0
    np.testing.assert_array_equal(
        wire[0], [0, spec.wire_total_bytes, spec.wire_total_bytes, 0])
    assert wire[1][0] == 0                  # DEAD: nothing on the wire
    assert wire[2][0] == bytes_full         # RESYNC: full-precision pull
    # non-live agents took no local step: per-agent loss/gn report 0
    assert mets["loss_agent"][1][0] == 0.0
    assert mets["loss_agent"][2][0] == 0.0
    assert mets["grad_norm_agent"][1][0] == 0.0
    assert mets["loss_agent"][1][1] > 0.0


def test_telemetry_never_perturbs_trajectory():
    """The no-perturbation invariant: the segment's final panels are
    BIT-identical with telemetry on or off (per-agent metrics are pure
    reads of arrays the round already materialized)."""
    m, H, S, dim, classes = 4, 2, 4, 12, 4
    init_params, loss_fn = _toy_problem(m, dim, classes)
    opt = make_optimizer("adamw", 1e-2)
    Ws, batches = _segment_inputs(S, H, m, dim, classes)
    finals, scalars = [], []
    for telemetry in (False, True):
        state, spec = dsgd.init_panel_state(init_params, opt, m,
                                            jax.random.PRNGKey(0),
                                            wire="int8")
        seg = dsgd.make_panel_segment(loss_fn, opt, H, spec,
                                      telemetry=telemetry)
        state, mets = seg(state, batches, Ws, jax.random.PRNGKey(7))
        finals.append(jax.device_get(state["panel"]))
        scalars.append({k: np.asarray(v) for k, v in mets.items()
                        if k in ("loss", "grad_norm", "grad_norm_max",
                                 "consensus")})
    for k in finals[0]:
        assert np.array_equal(finals[0][k], finals[1][k]), k
    for k in scalars[0]:
        np.testing.assert_array_equal(scalars[0][k], scalars[1][k])


def _tiny_lm_segment(scope_fn=None):
    """(compiled segment, its arguments) of a tiny olmo (2 layers, 2
    agents), traced with ``dsgd.scope`` replaced by ``scope_fn``."""
    from repro.configs import get_config
    from repro.models import build_model
    cfg = get_config("olmo-1b").reduced(d_model=64, vocab=64, layers=2)
    model = build_model(cfg)
    opt = make_optimizer("adamw", 1e-2)
    m, H, S, B, T = 2, 2, 2, 2, 8
    state, spec = dsgd.init_panel_state(model.init_params, opt, m,
                                        jax.random.PRNGKey(0))
    seg = dsgd.make_panel_segment(model.loss_fn, opt, H, spec)
    toks = jax.random.randint(jax.random.PRNGKey(3), (S, H, m, B, T + 1), 0,
                              cfg.vocab_size)
    batch = {"tokens": toks[..., :-1], "targets": toks[..., 1:],
             "mask": jnp.ones((S, H, m, B, T), jnp.float32)}
    Ws = jnp.full((S, m, m), 1.0 / m, jnp.float32)
    args = (state, batch, Ws, jax.random.PRNGKey(7))
    real = dsgd.scope
    if scope_fn is not None:
        dsgd.scope = scope_fn
    try:
        compiled = seg.lower(*args).compile()
    finally:
        dsgd.scope = real
    return compiled, args


def _scheduled_op_names(hlo_text):
    """op_name of every instruction a profiler can show: reducer regions
    (``to_apply=``) run inside their op and carry a bare name."""
    reducers = set(re.findall(r"to_apply=%([\w.\-]+)", hlo_text))
    names, comp = [], None
    for line in hlo_text.splitlines():
        if line and not line.startswith(" ") and line.rstrip().endswith("{"):
            comp = line.split()[1 if line.startswith("ENTRY") else 0]
            comp = comp.lstrip("%")
        mt = re.search(r'op_name="([^"]*)"', line)
        if mt and comp not in reducers:
            names.append(mt.group(1))
    return names


def test_fwd_bwd_scope_splits_the_local_step():
    """The model's forward and backward carry ``dsgd.fwd_bwd`` inside
    ``dsgd.local_grad``; the panel's ops, and the row relayout, do not."""
    compiled, _ = _tiny_lm_segment()
    names = _scheduled_op_names(compiled.as_text())
    fb = [n for n in names if "dsgd.fwd_bwd" in n]
    assert fb and all(re.search(r"dsgd\.local_grad/.*dsgd\.fwd_bwd", n)
                      for n in fb)
    assert any("transpose" in n for n in fb)  # the backward pass too
    assert not any("panel." in n for n in fb)
    assert any("dsgd.local_grad" in n and "dsgd.fwd_bwd" not in n
               for n in names)  # the relayout stays outside


def test_fwd_bwd_scope_keeps_trajectory_bit_identical():
    """A named scope is metadata: the segment's outputs are bit-identical
    to the same segment traced without ``dsgd.fwd_bwd``."""
    real = dsgd.scope

    def without_fwd_bwd(name):
        return (contextlib.nullcontext() if name == "dsgd.fwd_bwd"
                else real(name))
    outs = []
    for fn in (None, without_fwd_bwd):
        compiled, args = _tiny_lm_segment(fn)
        outs.append(jax.device_get(compiled(*args)))
    flat = [jax.tree_util.tree_leaves(o) for o in outs]
    assert len(flat[0]) == len(flat[1])
    for a, b in zip(*flat):
        np.testing.assert_array_equal(a, b)


def test_round_wire_bytes_unit():
    W = jnp.asarray(np.eye(4, dtype=np.float32))
    z = tmetrics.round_wire_bytes(W, bytes_wire=10, bytes_full=40)
    np.testing.assert_array_equal(np.asarray(z), 0)  # identity: all idle
    W = W.at[0, 0].set(0.5).at[0, 1].set(0.5)
    W = W.at[1, 1].set(0.5).at[1, 0].set(0.5)
    b = tmetrics.round_wire_bytes(W, bytes_wire=10, bytes_full=40)
    np.testing.assert_array_equal(np.asarray(b), [10, 10, 0, 0])
    # a delta codec's global round: communicating rows pay full storage
    b = tmetrics.round_wire_bytes(W, bytes_wire=10, bytes_full=40,
                                  full_bandwidth=jnp.asarray(True))
    np.testing.assert_array_equal(np.asarray(b), [40, 40, 0, 0])
    # liveness trits: DEAD pays 0, RESYNC pays the full pull
    lv = jnp.asarray([0, 1, 2, 1], jnp.int32)
    b = tmetrics.round_wire_bytes(W, bytes_wire=10, bytes_full=40, lv=lv)
    np.testing.assert_array_equal(np.asarray(b), [0, 10, 40, 0])


# ------------------------------------------------------------ event log


def _emit_rounds(log, lo, hi):
    for r in range(lo, hi):
        log.emit("round", round=r, loss=1.0 / (r + 1), grad_norm=0.5,
                 grad_norm_max=0.9, consensus=0.1, comm_cost_P=float(r))


def test_eventlog_stream_valid_and_deterministic(tmp_path):
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    for p in (pa, pb):
        with EventLog(p, run_id="abc") as log:
            log.emit("run_start", run_id="abc", schema=1,
                     config={"seed": 0})
            _emit_rounds(log, 0, 3)
            log.emit("merge", round=2, operator="uniform")
            log.emit("eval", round=2, merged_eval=0.3, local_eval=0.4)
            log.emit("run_end", rounds=3, final_loss=0.25, comm_cost_P=2.0)
    assert validate_stream(pa) == []
    with open(pa, "rb") as fa, open(pb, "rb") as fb:
        assert fa.read() == fb.read()  # byte-reproducible
    evs = read_events(pa)
    assert [e["seq"] for e in evs] == list(range(len(evs)))
    assert all("t" not in e for e in evs)  # no wall clock in the stream


def test_eventlog_rejects_schema_violations(tmp_path):
    log = EventLog(str(tmp_path / "e.jsonl"))
    with pytest.raises(ValueError, match="unknown event type"):
        log.emit("nope", x=1)
    with pytest.raises(ValueError, match="missing required field"):
        log.emit("round", round=0, loss=1.0)
    with pytest.raises(ValueError, match="unknown field"):
        log.emit("merge", round=0, operator="uniform", wallclock=1.23)
    with pytest.raises(ValueError, match="is not a"):
        log.emit("merge", round="zero", operator="uniform")
    # per-agent columns are typed lists
    with pytest.raises(ValueError, match="live"):
        log.emit("round", round=0, loss=1.0, grad_norm=0.5,
                 grad_norm_max=0.9, consensus=0.1, comm_cost_P=0.0,
                 live=[1.5, 2.5])
    log.close()
    assert not os.path.getsize(str(tmp_path / "e.jsonl"))


def test_validate_stream_catches_gaps_and_round_dups(tmp_path):
    p = str(tmp_path / "bad.jsonl")
    rec = {"type": "round", "round": 1, "loss": 1.0, "grad_norm": 0.1,
           "grad_norm_max": 0.1, "consensus": 0.0, "comm_cost_P": 0.0}
    with open(p, "w") as f:
        f.write(json.dumps({**rec, "seq": 0}) + "\n")
        f.write(json.dumps({**rec, "seq": 2}) + "\n")   # seq gap
        f.write(json.dumps({**rec, "seq": 2}) + "\n")   # duplicated round
    errs = validate_stream(p)
    assert any("seq" in e for e in errs)
    assert any("duplicated or missing round" in e for e in errs)


def test_eventlog_truncate_resume_byte_identical(tmp_path):
    """The fault_smoke contract in miniature: a stream interrupted after
    round 1 and resumed (truncate back to the checkpointed seq, re-emit
    the replayed rounds) ends byte-identical to the uninterrupted one."""
    pa, pb = str(tmp_path / "base.jsonl"), str(tmp_path / "kill.jsonl")
    with EventLog(pa, run_id="r") as log:
        log.emit("run_start", run_id="r", schema=1, config={})
        _emit_rounds(log, 0, 4)
        log.emit("run_end", rounds=4, final_loss=0.2, comm_cost_P=3.0)

    with EventLog(pb, run_id="r") as log:      # first life: dies after
        log.emit("run_start", run_id="r", schema=1, config={})
        _emit_rounds(log, 0, 2)                # rounds 0,1 emitted
    # "checkpoint" was taken at seq=2 (run_start + round 0): the second
    # life truncates back and replays round 1 exactly once
    with EventLog(pb, run_id="r", resume_at=2) as log:
        assert log.seq == 2
        _emit_rounds(log, 1, 4)
        log.emit("run_end", rounds=4, final_loss=0.2, comm_cost_P=3.0)
    with open(pa, "rb") as fa, open(pb, "rb") as fb:
        assert fa.read() == fb.read()
    assert validate_stream(pb) == []
    # the sidecar keeps BOTH lives (operational history, never compared)
    assert os.path.exists(wall_path(pb))


def test_eventlog_truncate_refuses_short_file(tmp_path):
    p = str(tmp_path / "s.jsonl")
    with EventLog(p) as log:
        _emit_rounds(log, 0, 2)
    with pytest.raises(ValueError, match="expects 5 events"):
        EventLog.truncate_file(p, 5)
    with pytest.raises(FileNotFoundError):
        EventLog.truncate_file(str(tmp_path / "missing.jsonl"), 3)
    assert EventLog.truncate_file(str(tmp_path / "missing.jsonl"), 0) == 0


def test_emit_op_goes_to_sidecar_only(tmp_path):
    p = str(tmp_path / "e.jsonl")
    with EventLog(p, run_id="r") as log:
        log.emit("run_start", run_id="r", schema=1, config={})
        log.emit_op("checkpoint_save", step=3, bytes=100, dt=0.5)
        log.emit("run_end", rounds=0, final_loss=0.0, comm_cost_P=0.0)
    assert len(read_events(p)) == 2  # sidecar records never in-stream
    wall = read_events(wall_path(p))
    ops = [w for w in wall if w.get("op") == "checkpoint_save"]
    assert len(ops) == 1 and ops[0]["step"] == 3 and "t" in ops[0]
    assert validate_stream(p) == []


def test_make_run_id_deterministic():
    a = make_run_id({"seed": 0, "arch": "olmo-1b"})
    b = make_run_id({"arch": "olmo-1b", "seed": 0})  # key order ignored
    assert a == b and len(a) == 12 and int(a, 16) >= 0
    assert make_run_id({"seed": 1, "arch": "olmo-1b"}) != a


# ----------------------------------------------------- latency histogram


def test_histogram_percentiles_and_weights():
    h = Histogram()
    for _ in range(50):
        h.record(1e-3)
    h.record(1e-1, n=50)  # weighted record: one value, 50 counts
    assert h.n == 100
    assert h.mean == pytest.approx(0.0505, rel=1e-6)
    assert h.vmin == 1e-3 and h.vmax == 1e-1
    assert h.percentile(50) <= 2e-3      # inside the 1 ms bucket
    assert h.percentile(90) >= 5e-2      # inside the 100 ms bucket
    assert h.percentile(0) == 1e-3       # clamped to observed min
    assert h.percentile(100) == 1e-1
    s = h.summary()
    assert s["count"] == 100 and s["p50_s"] <= s["p90_s"] <= s["p99_s"]
    su = h.summary_us()
    assert su["p50_us"] == pytest.approx(s["p50_s"] * 1e6, rel=1e-3)
    assert sum(h.to_dict()["buckets"].values()) == 100


def test_histogram_reset_and_merge():
    h = Histogram()
    h.record(1e-3, n=5)
    h.reset()
    assert h.n == 0 and h.summary() == {"count": 0}
    assert h.percentile(50) == 0.0
    a, b = Histogram(), Histogram()
    a.record(1e-3, n=2)
    b.record(1e-2, n=3)
    a.merge(b)
    assert a.n == 5 and a.vmax == 1e-2
    # ladder mismatches refuse loudly, naming the divergence: a length
    # mismatch reports both sizes, an equal-length value mismatch names
    # the first differing index and both bounds (merging across ladders
    # would silently mis-bin every sample)
    with pytest.raises(ValueError, match=r"65 bounds vs 2"):
        a.merge(Histogram(bounds=np.array([1.0, 2.0])))
    skewed = default_bounds()
    skewed[3] *= 1.1  # still increasing (ladder step is ~1.33x)
    with pytest.raises(ValueError, match=r"index 3 \(") as ei:
        a.merge(Histogram(bounds=skewed))
    assert "vs" in str(ei.value)
    with pytest.raises(ValueError, match="increasing"):
        Histogram(bounds=np.array([2.0, 1.0]))
    assert set(histogram_set(("x", "y"))) == {"x", "y"}


# ------------------------------------------- serving engine counters


@pytest.mark.serve
def test_engine_snapshot_reset_pins_occupancy(tmp_path):
    """Regression: ServingEngine.stats was never resettable, so
    occupancy averaged over warmup/compile ticks. reset() discards them;
    a full-occupancy run afterwards must report exactly 1.0, and the
    latency histograms must count only post-reset activity. The request
    lifecycle also lands in the event stream, schema-valid."""
    from repro.configs import get_config
    from repro.models import build_model
    from repro.serving import Request, ServingEngine

    cfg = get_config("olmo-1b").reduced(d_model=64, vocab=64, layers=2)
    model = build_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    ev = str(tmp_path / "serve.jsonl")
    log = EventLog(ev, run_id="t")
    eng = ServingEngine(model, params, max_concurrency=2, max_len=48,
                        events=log)

    def reqs(rids, max_new):
        out = []
        for rid in rids:
            toks = np.asarray(jax.random.randint(
                jax.random.fold_in(jax.random.PRNGKey(1), rid), (8,), 0,
                cfg.vocab_size), np.int32)
            out.append(Request(rid=rid, tokens=toks, max_new=max_new))
        return out

    eng.serve(reqs([100], 2))     # warmup: compile ticks pollute stats
    assert eng.snapshot()["ticks"] >= 1
    eng.reset()
    assert eng.snapshot()["ticks"] == 0
    assert eng.hists["ttft_s"].n == 0

    out = eng.serve(reqs([0, 1], 4))
    assert {len(v) for v in out.values()} == {4}
    snap = eng.snapshot()
    # both slots admitted up front, retired together: every tick is full
    assert snap["ticks"] == 3     # prefill emits tok 1; 3 decode steps
    assert snap["occupancy"] == 1.0
    lat = snap["latency"]
    assert lat["ttft_s"]["count"] == 2
    assert lat["queue_wait_s"]["count"] == 2
    assert lat["decode_step_s"]["count"] == 3
    assert lat["per_token_s"]["count"] == 2
    assert lat["ttft_s"]["p50_s"] > 0
    assert snap["histograms"]["ttft_s"]["buckets"]
    log.close()
    assert validate_stream(ev) == []
    kinds = [e["type"] for e in read_events(ev)]
    assert kinds.count("request_submit") == 3   # warmup + 2
    assert kinds.count("request_admit") == 3
    assert kinds.count("request_retire") == 3
