"""The reduction from a trace to the per-layer numbers: busy union, idle
share, scope and module attribution, collective time not hidden under
compute, and the parsing of the chip's op names and compiled text."""
import json
import os

import pytest

from bench import trace

HERE = os.path.dirname(os.path.abspath(__file__))
MS = 1_000_000

# two devices over a 10 ms window; ops as [name, t0, t1, scope, opcode]
TRACE = {
    "host": [["bench.window", 0, 10 * MS], ["bench.dispatch", 0, 1 * MS],
             ["bench.wait", 8 * MS, 10 * MS]],
    "modules": {"/device:TPU:0": [["jit_segment", 1 * MS, 8 * MS]],
                "/device:TPU:1": [["jit_segment", 1 * MS, 8 * MS]]},
    "devices": {
        "/device:TPU:0": [
            ["fusion.1", 1 * MS, 4 * MS, "jit(segment)/dsgd.local_grad/dot",
             "fusion"],
            ["fusion.2", 3 * MS, 5 * MS, "jit(segment)/dsgd.local_update/add",
             "fusion"],
            ["all-gather.3", 4 * MS, 7 * MS, "jit(segment)/panel.mix_mean/ag",
             "all-gather"],
            ["copy.4", 9 * MS, 12 * MS, "", "copy"]],  # ends past the window
        "/device:TPU:1": [
            ["fusion.1", 1 * MS, 6 * MS, "jit(segment)/dsgd.local_grad/dot",
             "fusion"]],
    },
}
SCOPES = {"grad": ["dsgd.local_grad"], "update": ["dsgd.local_update"],
          "mix": ["panel."]}


def test_interval_arithmetic():
    assert trace.union([[5, 6], [0, 2], [1, 3]]) == [[0, 3], [5, 6]]
    assert trace.total([[0, 2], [1, 3], [5, 6]]) == 4
    assert trace.minus([[0, 10]], [[2, 3], [5, 7]]) == \
        [[0, 2], [3, 5], [7, 10]]
    assert trace.gaps([[2, 3]], 0, 4) == [[0, 2], [3, 4]]
    assert trace.clip([[-1, 2], [3, 9]], 0, 5) == [[0, 2], [3, 5]]


def test_reduce_small_trace():
    red = trace.reduce(TRACE, SCOPES, {"seg": ["jit_segment"]})
    assert red["window_ns"] == 10 * MS
    d0, d1 = red["devices"]["/device:TPU:0"], red["devices"]["/device:TPU:1"]
    # 1..7 ms and 9..10 ms
    assert d0["busy_ns"] == 7 * MS and d1["busy_ns"] == 5 * MS
    assert d0["scope_ns"] == {"grad": 3 * MS, "update": 2 * MS, "mix": 3 * MS}
    # the all-gather runs 4..7, compute covers 4..5
    assert d0["collective_ns"] == 3 * MS
    assert d0["collective_exposed_ns"] == 2 * MS
    assert d1["collective_exposed_ns"] == 0
    assert d0["module_ns"] == {"seg": 6 * MS}
    # the longest gap (device 1, 6..10 ms) falls mostly in bench.wait
    assert red["idle_gaps"][0] == ["bench.wait", pytest.approx(0.004)]
    assert red["device_ops"][0][0] == "fusion dsgd.local_grad/dot"


def test_idle_share_reader():
    from bench import common
    red = trace.reduce(TRACE, SCOPES)
    mod = common.load_metric_reader("train.idle_share")
    assert mod.read({"reduced": red}) == pytest.approx(40.0)


def test_serve_idle_share_reader():
    from bench import common
    red = trace.reduce(TRACE, SCOPES)
    mod = common.load_metric_reader("serve.idle_share")
    # devices busy 7 and 5 ms of a 10 ms window: 6 ms on average
    assert mod.read({"reduced": red}) == pytest.approx(40.0)
    assert mod.read({"reduced": {"devices": {}, "window_ns": 0}}) is None


def test_op_names_and_event_names():
    hlo = ('  %fusion.357 = (bf16[4,8]{1,0}, f32[4,8]{1,0}) fusion(f32[4,8]'
           '{1,0} %a), kind=kLoop, calls=%fc, metadata={op_name="jit(segment)'
           '/while/body/dsgd.local_update/mul" stack_frame_id=3}\n'
           '  ROOT %add.4 = f32[64]{0} add(%x, %y), metadata={op_name='
           '"jit(f)/panel.mix/add"}\n  %p = f32[2] parameter(0)\n')
    names = trace.op_names(hlo)
    assert names == {"fusion.357": "jit(segment)/while/body/dsgd.local_update"
                                   "/mul", "add.4": "jit(f)/panel.mix/add"}
    ev = ("%while.128 = (s32[]{:T(128)}, f32[4,170393600]{1,0:T(4,128)}) "
          "while((s32[]{:T(128)}, f32[4,170393600]{1,0:T(4,128)}) %tuple), "
          "condition=%c, body=%b")
    assert trace._EVENT.match(ev).groups() == ("while.128", "while")
    ev = ("%fusion.411 = bf16[170393600]{0:T(1024)(128)(2,1)} fusion(f32[]"
          "{:T(128)} %broadcast.881), kind=kLoop")
    assert trace._EVENT.match(ev).groups() == ("fusion.411", "fusion")


def test_recorded_trace():
    """A window recorded on a v5e (training cell, trimmed): the reduction
    gives the numbers that were read from it at the time."""
    path = os.path.join(HERE, "data", "recorded_trace.json")
    with open(path) as f:
        rec = json.load(f)
    red = trace.reduce(rec["trace"], rec["scopes"], rec.get("modules"))
    for dev, want in rec["expect"].items():
        got = red["devices"][dev]
        assert got["busy_ns"] == want["busy_ns"]
        assert got["scope_ns"] == want["scope_ns"]
    assert red["window_ns"] == rec["expect_window_ns"]
