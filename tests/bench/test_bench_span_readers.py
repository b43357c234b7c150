"""The readers of the local step's split and of the serving engine's host
time: each gives the number a small synthetic trace or engine record
holds, and nothing where the program or the harness gives it nothing to
read."""
import pytest

from bench import common, trace

MS = 1_000_000


def _read(name, ctx):
    return common.load_metric_reader(name).read(ctx)


# one device over a 10 ms window: the local step's forward/backward (1..4
# ms), its relayout (4..6 ms, under dsgd.local_grad only), the update
TRAIN = {
    "host": [["bench.window", 0, 10 * MS]],
    "modules": {},
    "devices": {"/device:TPU:0": [
        ["fusion.1", 1 * MS, 4 * MS,
         "jit(segment)/dsgd.local_grad/while/body/dsgd.fwd_bwd/dot", "fusion"],
        ["dynamic-update-slice.2", 4 * MS, 6 * MS,
         "jit(segment)/dsgd.local_grad/while/body/dynamic_update_slice",
         "dynamic-update-slice"],
        ["fusion.3", 6 * MS, 7 * MS, "jit(segment)/dsgd.local_update/add",
         "fusion"]]},
}
SCOPES = {"local_grad": ["dsgd.local_grad"],
          "local_update": ["dsgd.local_update"],
          "mix": ["panel.", "merge.panel"], "fwd_bwd": ["dsgd.fwd_bwd"]}


def _train_ctx(scopes, steps=2):
    return {"reduced": trace.reduce(TRAIN, scopes),
            "counts": {"local_steps": steps}}


def test_local_step_split_adds_up():
    ctx = _train_ctx(SCOPES)
    fb = _read("train.fwd_bwd_ms", ctx)
    rel = _read("train.relayout_ms", ctx)
    assert fb == pytest.approx(1.5) and rel == pytest.approx(1.0)
    assert fb + rel == pytest.approx(_read("train.local_grad_ms", ctx))


def test_local_step_split_reads_nothing_without_the_scope():
    # the harness without the label, or a program without the scope
    no_label = {k: v for k, v in SCOPES.items() if k != "fwd_bwd"}
    for ctx in (_train_ctx(no_label),
                _train_ctx({**SCOPES, "fwd_bwd": ["dsgd.nowhere"]}),
                _train_ctx(SCOPES, steps=0)):
        assert _read("train.fwd_bwd_ms", ctx) is None
        assert _read("train.relayout_ms", ctx) is None


def _records():
    # arrival, admission start, first token (seconds); medians 0.020, 0.015
    times = [(0.0, 0.010, 0.020), (1.0, 1.020, 1.035), (2.0, 2.040, 2.060)]
    return [{"rid": i, "arrival": a, "admit": s, "first": f, "retire": None,
             "tokens": 1} for i, (a, s, f) in enumerate(times)]


def test_engine_record_readers():
    ctx = {"engine": {"requests": _records()}}
    assert _read("serve.queue_wait_ms", ctx) == pytest.approx(20.0)
    assert _read("serve.first_token_ms", ctx) == pytest.approx(15.0)


@pytest.mark.parametrize("ctx", [{}, {"engine": {"requests": []}},
                                 {"engine": {}}])
def test_engine_record_readers_without_records(ctx):
    assert _read("serve.queue_wait_ms", ctx) is None
    assert _read("serve.first_token_ms", ctx) is None


def test_ttft_reader_is_the_median_of_every_request():
    # seconds from due time to first token, one request at the window's end
    ctx = {"ttft": [0.040, 0.020, 0.060, 0.050, 3.0]}
    assert _read("serve.ttft_p50_ms", ctx) == pytest.approx(50.0)
    assert _read("serve.ttft_p50_ms", {"ttft": []}) is None
    assert _read("serve.ttft_p50_ms", {}) is None


# two devices over a 10 ms window. The engine's spans: a step 1..5 ms
# (device busy 2..4) and an admission 6..8 ms (device 0 busy 6..7,
# device 1 idle). The Python tracer's event of the caller's loop covers
# the whole window, and is not the engine's.
SERVE = {
    "host": [["bench.window", 0, 10 * MS],
             ["_serve.py:69_window", 0, 10 * MS],
             ["serve.step", 1 * MS, 5 * MS], ["serve.fetch", 3 * MS, 5 * MS],
             ["serve.admit", 6 * MS, 8 * MS],
             ["serve.first_token", 7 * MS, 8 * MS]],
    "modules": {},
    "devices": {
        "/device:TPU:0": [["fusion.1", 2 * MS, 4 * MS, "", "fusion"],
                          ["fusion.2", 6 * MS, 7 * MS, "", "fusion"]],
        "/device:TPU:1": [["fusion.1", 2 * MS, 4 * MS, "", "fusion"]]},
}


def test_engine_idle_share():
    # device 0: idle 1..2, 4..5, 7..8 under the spans (3 ms); device 1:
    # 1..2, 4..5, 6..8 (4 ms); mean 3.5 ms of 10
    share = _read("serve.engine_idle_share", {"extract": SERVE})
    assert share == pytest.approx(35.0)
    whole = _read("serve.idle_share",
                  {"reduced": trace.reduce(SERVE, {})})
    assert share <= whole


def test_engine_idle_share_reads_nothing_without_spans():
    bare = dict(SERVE, host=[h for h in SERVE["host"]
                             if not h[0].startswith("serve.")])
    assert _read("serve.engine_idle_share", {"extract": bare}) is None
    assert _read("serve.engine_idle_share", {}) is None
    assert _read("serve.engine_idle_share",
                 {"extract": dict(SERVE, devices={})}) is None


@pytest.mark.parametrize("seed", range(5))
def test_engine_idle_share_matches_interval_difference(seed):
    """The reader's one-sweep overlap gives what the trace module's
    interval difference gives, on random spans and ops."""
    import random
    rnd = random.Random(seed)

    def intervals(n):
        out = []
        for _ in range(n):
            a = rnd.randrange(0, 10 * MS)
            out.append([a, a + rnd.randrange(1, MS)])
        return out
    names = ["serve.step", "serve.admit", "serve.fetch", "other"]
    host = [["bench.window", 0, 10 * MS]] + [
        [rnd.choice(names), a, b] for a, b in intervals(40)]
    ops = [["op", a, b, "", "fusion"] for a, b in intervals(200)]
    ex = {"host": host, "modules": {}, "devices": {"/device:TPU:0": ops}}
    spans = trace.clip([[a, b] for n, a, b in host
                        if n in ("serve.step", "serve.admit")], 0, 10 * MS)
    want = trace.total(trace.minus(spans, [[o[1], o[2]] for o in ops]))
    got = _read("serve.engine_idle_share", {"extract": ex})
    assert got == pytest.approx(100.0 * want / (10 * MS))
