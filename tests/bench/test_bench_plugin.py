"""A second model family enters the benchmark through new files and
entries alone, as a change that adds a model would bring it: in a copy
of the checkout, a family module, its configuration files, limits, a
small traffic mix, two cells and a per-layer reader are added
(``data/plugin/``), and a training cell and a serving cell of that family
run on the CPU with ``correct`` true; traced, the training cell's new
reader reads the scope label it declares and a counter of the program.
No file of the harness that was there changes."""
import hashlib
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
PLUGIN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                      "plugin")


def _hashes(top):
    out = {}
    for d, dirs, files in os.walk(top):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, top)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def _run(root, cell, trace=0):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, '.'); from bench import run; "
         "run.main(sys.argv[1:], require_tpu=False)",
         "--workload", cell, "--seed", str(2 ** 33 + 17), "--seconds", "2",
         "--trace", str(trace)],
        cwd=root, env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.fixture(scope="module")
def plugged(tmp_path_factory):
    """(root, results by cell, hashes of bench/ before and after)."""
    root = str(tmp_path_factory.mktemp("plugin") / "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(ROOT, "src"), os.path.join(root, "src"))
    before = _hashes(os.path.join(root, "bench"))
    assert before == _hashes(os.path.join(ROOT, "bench"))
    for d, _, files in os.walk(os.path.join(PLUGIN, "bench")):
        for f in files:
            rel = os.path.relpath(os.path.join(d, f), PLUGIN)
            dst = os.path.join(root, rel)
            assert not os.path.exists(dst), rel  # new files only
            os.makedirs(os.path.dirname(dst), exist_ok=True)
            shutil.copy(os.path.join(d, f), dst)
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with open(os.path.join(PLUGIN, "benchmark_entries.json")) as f:
        entries = json.load(f)
    for key, items in entries.items():
        bench[key] += items
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    results = {w["name"]: _run(root, w["name"])
               for w in entries["workloads"]}
    results["tinyllama.allreduce", "traced"] = _run(
        root, "tinyllama.allreduce", trace=1)
    return root, results, before, _hashes(os.path.join(root, "bench"))


def test_training_cell_of_a_second_family_is_correct(plugged):
    res = plugged[1]["tinyllama.allreduce"]
    assert res["correct"] is True
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0


def test_serving_cell_of_a_second_family_is_correct(plugged):
    res = plugged[1]["tinyllama.serve"]
    assert res["correct"] is True
    assert res["checks"]["checked_tokens"]["value"] >= 10


def test_reader_of_a_second_family_reads_its_scope_and_a_counter(plugged):
    """The reader came as a new file: the trace was reduced by the label
    it declares, and it read the segment's per-round gradient norms of
    every call of the window from ``ctx["program"]``."""
    res = plugged[1]["tinyllama.allreduce", "traced"]
    assert res["correct"] is True
    assert set(res["metrics"]) == {"train.plugin_rounds"}
    assert res["metrics"]["train.plugin_rounds"] == {
        "value": 8.0 * res["attempted"], "unit": "rounds"}


def test_no_harness_file_changed(plugged):
    _, _, before, after = plugged
    assert {k: after[k] for k in before} == before
    added = sorted(set(after) - set(before))
    assert added and all(
        k.startswith(("models/", "configs/", "limits/", "traffic/",
                      "metrics/"))
        for k in added), added
    assert "metrics/train.plugin_rounds.py" in added


def test_the_second_family_has_its_own_tree():
    sys.path.insert(0, ROOT)
    from bench import common
    cfg = common.load_json(os.path.join(
        PLUGIN, "bench", "configs", "tinyllama-train.json"))
    fam = common.family(cfg, root=PLUGIN)
    olmo = common.family({"model_type": "olmo"})
    assert set(fam.shapes(cfg)) - set(olmo.shapes(cfg)) == {
        "head", "final_norm", "norm1", "norm2"}
    # an untied head: the embedding table is held beside the head that
    # every token multiplies; OLMo's tied table is counted once
    assert fam.params(cfg) - fam.matmul_params(cfg) == \
        32 * 256 + (2 * 2 + 1) * 32
    assert olmo.params(cfg) == olmo.matmul_params(cfg)
