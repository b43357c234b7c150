"""train.collective_exposed_ms on a small synthetic trace of two chips: a
collective that compute overlaps counts only where it sticks out, an
exposed one counts whole, the busiest chip is read, per round; the
async start and done forms are collectives; a window with no collective
reads nothing."""
import pytest

from bench import common, trace

MS = 1_000_000
NAME = "train.collective_exposed_ms"


def _trace(dev1_ops):
    return {
        "host": [["bench.window", 0, 10 * MS]],
        "modules": {},
        "devices": {
            # compute 0..4; an all-gather 2..5 (4..5 exposed); an async
            # all-reduce 6..8 with nothing beside it (exposed whole)
            "/device:TPU:0": [
                ["fusion.1", 0, 4 * MS, "jit(segment)/dsgd.local_grad/dot",
                 "fusion"],
                ["all-gather.2", 2 * MS, 5 * MS,
                 "jit(segment)/panel.mix_mean/ag", "all-gather"],
                ["all-reduce-start.3", 6 * MS, 7 * MS,
                 "jit(segment)/panel.mix_mean/ar", "all-reduce-start"],
                ["all-reduce-done.3", 7 * MS, 8 * MS,
                 "jit(segment)/panel.mix_mean/ar", "all-reduce-done"]],
            "/device:TPU:1": dev1_ops,
        },
    }


# compute 0..3 hides an all-reduce 1..2 whole
HIDDEN = [["fusion.1", 0, 3 * MS, "jit(segment)/dsgd.local_grad/dot",
           "fusion"],
          ["all-reduce.2", 1 * MS, 2 * MS, "jit(segment)/panel.x/ar",
           "all-reduce"]]


def _read(ex, rounds=2):
    red = trace.reduce(ex, {})
    return common.load_metric_reader(NAME).read(
        {"reduced": red, "counts": {"rounds": rounds}})


def test_exposed_collective_per_round_on_the_busiest_chip():
    red = trace.reduce(_trace(HIDDEN), {})
    d0, d1 = red["devices"]["/device:TPU:0"], red["devices"]["/device:TPU:1"]
    assert d0["collective_ns"] == 5 * MS
    assert d0["collective_exposed_ns"] == 3 * MS
    assert d1["collective_ns"] == 1 * MS and d1["collective_exposed_ns"] == 0
    assert _read(_trace(HIDDEN)) == pytest.approx(1.5)
    assert _read(_trace(HIDDEN), rounds=3) == pytest.approx(1.0)


def test_hidden_collectives_read_zero():
    ex = _trace(HIDDEN)
    ex["devices"].pop("/device:TPU:0")
    assert _read(ex) == 0.0


def test_no_collective_reads_nothing():
    ex = _trace([])
    ex["devices"] = {"/device:TPU:0": ex["devices"]["/device:TPU:0"][:1]}
    assert _read(ex) is None
    assert _read(_trace(HIDDEN), rounds=0) is None


@pytest.mark.parametrize("opcode,is_collective", [
    ("all-reduce", True), ("all-reduce-start", True),
    ("all-reduce-done", True), ("all-gather-start", True),
    ("all-gather-done", True), ("reduce-scatter", True),
    ("collective-permute-start", True), ("all-to-all", True),
    ("fusion", False), ("reduce", False), ("copy", False)])
def test_collective_opcodes(opcode, is_collective):
    assert bool(trace.COLLECTIVE.match(opcode)) is is_collective
