"""A per-layer reader says what it reads from the trace: the scope labels
(``SCOPES``) and compiled programs (``MODULES``) it declares are what the
harness reduces the trace by. A new reader file gets its own label's
time; two readers that give one label different keys stop the run before
JAX takes a chip; the readers of the cells hold the labels the harness
used to fix, key for key; and the segment's own metrics of every call
reach the readers concatenated."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import common, trace

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
MS = 1_000_000

# one device over a 10 ms window: forward/backward 1..4 ms, the rest of
# the local step 4..6, the update 6..7, the mix 7..9
TRACE = {
    "host": [["bench.window", 0, 10 * MS]],
    "modules": {"/device:TPU:0": [["jit_segment", 1 * MS, 9 * MS]]},
    "devices": {"/device:TPU:0": [
        ["fusion.1", 1 * MS, 4 * MS,
         "jit(segment)/dsgd.local_grad/while/body/dsgd.fwd_bwd/moe/dot",
         "fusion"],
        ["fusion.2", 4 * MS, 6 * MS, "jit(segment)/dsgd.local_grad/slice",
         "fusion"],
        ["fusion.3", 6 * MS, 7 * MS, "jit(segment)/dsgd.local_update/add",
         "fusion"],
        ["fusion.4", 7 * MS, 9 * MS, "jit(segment)/panel.mix_mean/dot",
         "fusion"]]},
}
PROBE = '''
SCOPES = {"moe": ["/moe/"], %s}


def read(ctx):
    t = max(d["scope_ns"]["moe"] for d in ctx["reduced"]["devices"].values())
    return t / ctx["counts"]["local_steps"] / 1e6 if t else None
'''
# the labels bench/train.py and bench/serve.py fixed before readers
# declared them
OLD_SCOPES = {"local_grad": ["dsgd.local_grad"], "fwd_bwd": ["dsgd.fwd_bwd"],
              "local_update": ["dsgd.local_update"],
              "mix": ["panel.", "merge.panel"]}
OLD_MODULES = {"prefill": ["jit_prefill"], "decode": ["jit_step_fn"]}


def _checkout(tmp_path, probe_keys=""):
    """A copy of the benchmark with a new reader file ``train.probe_ms``."""
    root = tmp_path / "checkout"
    shutil.copytree(os.path.join(ROOT, "bench"), root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    (root / "bench" / "metrics" / "train.probe_ms.py").write_text(
        PROBE % probe_keys)
    return str(root)


def test_new_reader_gets_the_time_of_the_label_it_declares(tmp_path):
    root = _checkout(tmp_path)
    per_layer = [{"name": "train.local_grad_ms"}, {"name": "train.mix_ms"},
                 {"name": "train.probe_ms"}]
    scopes = common.declarations(per_layer, "SCOPES", root)
    assert scopes == {"local_grad": ["dsgd.local_grad"], "moe": ["/moe/"],
                      "mix": ["panel.", "merge.panel"]}
    red = trace.reduce(TRACE, scopes)
    assert red["scopes"] == scopes
    assert red["devices"]["/device:TPU:0"]["scope_ns"] == {
        "local_grad": 5 * MS, "moe": 3 * MS, "mix": 2 * MS}
    ctx = {"reduced": red, "counts": {"local_steps": 2, "rounds": 2}}
    read = {m["name"]: common.load_metric_reader(m["name"], root).read(ctx)
            for m in per_layer}
    assert read == pytest.approx({"train.local_grad_ms": 2.5,
                                  "train.mix_ms": 1.0,
                                  "train.probe_ms": 1.5})


def test_a_label_shared_with_the_same_keys_is_one_label(tmp_path):
    root = _checkout(tmp_path, '"fwd_bwd": ["dsgd.fwd_bwd"]')
    scopes = common.declarations(
        [{"name": "train.fwd_bwd_ms"}, {"name": "train.relayout_ms"},
         {"name": "train.probe_ms"}], "SCOPES", root)
    assert scopes["fwd_bwd"] == ["dsgd.fwd_bwd"]
    assert set(scopes) == {"fwd_bwd", "local_grad", "moe"}


def test_a_label_clash_names_both_readers(tmp_path):
    root = _checkout(tmp_path, '"local_grad": ["dsgd.elsewhere"]')
    with pytest.raises(SystemExit) as e:
        common.declarations([{"name": "train.local_grad_ms"},
                             {"name": "train.probe_ms"}], "SCOPES", root)
    msg = str(e.value)
    assert "train.local_grad_ms" in msg and "train.probe_ms" in msg
    assert "'local_grad'" in msg


def test_a_label_clash_stops_the_run_before_any_chip(tmp_path):
    """The command stops on the clash, before it looks for a chip, and
    prints no result."""
    root = _checkout(tmp_path, '"mix": ["panel.mix"]')
    bench = common.load_benchmark(root)
    cell = bench["workloads"][0]["name"]
    bench["per_layer"].append({
        "name": "train.probe_ms", "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "local forward and backward",
        "moves": "train_tokens_per_s", "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", cell, "--seed", "1",
         "--seconds", "1", "--trace", "0"],
        cwd=root, env=env, capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert "train.mix_ms" in p.stderr and "train.probe_ms" in p.stderr
    assert "no TPU" not in p.stderr
    assert "metrics" not in p.stdout


@pytest.mark.parametrize("kind,attr,old", [("train", "SCOPES", OLD_SCOPES),
                                           ("serve", "MODULES", OLD_MODULES)])
def test_cells_reduce_by_the_labels_the_harness_fixed(kind, attr, old):
    """Every cell's readers together declare exactly the labels the
    harness reduced by before, key for key, and nothing else: each
    reading stays as it was."""
    bench = common.load_benchmark()
    for w in bench["workloads"]:
        if not w["traffic"].startswith({"train": "dsgd",
                                        "serve": "serve"}[kind]):
            continue
        per_layer = common.cell_metrics(bench, w["name"], "per_layer")
        got = common.declarations(per_layer, attr)
        assert got == old, w["name"]
        other = "MODULES" if attr == "SCOPES" else "SCOPES"
        assert common.declarations(per_layer, other) == {}


def test_program_counters_concatenate_every_call():
    from bench import train
    calls = [{"loss": np.array([1.0, 2.0], np.float32),
              "held": {"tokens": np.array([[3, 4]], np.int32)},
              "count": np.int32(5)},
             {"loss": np.array([3.0, 4.0], np.float32),
              "held": {"tokens": np.array([[5, 6]], np.int32)},
              "count": np.int32(7)}]
    got = train.program_counters(calls)
    np.testing.assert_array_equal(got["loss"], [1.0, 2.0, 3.0, 4.0])
    np.testing.assert_array_equal(got["held"]["tokens"], [[3, 4], [5, 6]])
    np.testing.assert_array_equal(got["count"], [5, 7])
    assert isinstance(got["loss"], np.ndarray)
