"""The harness finds every cell of BENCHMARK.json by name, and the files
it names hold what the harness needs; a configuration and a traffic mix
it has never seen are picked up by adding files alone."""
import json
import os
import re

import pytest

from bench import common

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
BENCH = common.load_benchmark(ROOT)
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
CELLS = [w["name"] for w in BENCH["workloads"]]
WIDTHS = re.compile(r"(_dim|_rank)$|^hidden_size$|intermediate|head_|"
                    r"latent|state|proj|expan|experts_per_tok")


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert BENCH["command"] == ["python3", "bench/run.py"]
    assert 1 <= BENCH["run_seconds"] <= 51
    for p in BENCH["paths"]:
        assert os.path.isdir(os.path.join(ROOT, p))
        assert re.fullmatch(r"[A-Za-z0-9_./-]{1,200}", p)


def test_names_units_and_bounds():
    names = []
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"])
        assert m["better"] in ("lower", "higher")
        names.append(m["name"])
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    e2e = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        assert "\n" not in m["layer"] and len(m["layer"]) <= 200
        assert set(m["workloads"]) <= set(CELLS)
        if "mfu" in m["name"] or m["name"].endswith("_roofline"):
            assert m["unit"] == "%"
    names += [c["name"] for c in BENCH["configs"]] + CELLS
    assert len(names) == len(set(names))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_loads_by_name(cell):
    w, cfg, traffic, _ = common.find_cell(cell, ROOT)
    assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert NAME.match(w["config"]) and NAME.match(w["traffic"])
    assert traffic["kind"] in ("train", "serve")
    lim = common.load_json(os.path.join(ROOT, "bench", "limits",
                                        cell + ".json"))
    assert lim["limits"] and lim["readings"]
    e2e = common.cell_metrics(BENCH, cell, "end_to_end")
    assert "setup_s" in [m["name"] for m in e2e] and len(e2e) >= 2
    per_layer = common.cell_metrics(BENCH, cell, "per_layer")
    assert per_layer
    for m in per_layer:
        assert callable(common.load_metric_reader(m["name"], ROOT).read)
        assert m["moves"] in [x["name"] for x in e2e]


@pytest.mark.parametrize("entry", BENCH["configs"],
                         ids=[c["name"] for c in BENCH["configs"]])
def test_config_file(entry):
    cfg = common.load_json(os.path.join(ROOT, entry["file"]))
    assert entry["file"].startswith("bench/configs/")
    assert cfg["name"] == entry["name"]
    assert cfg["source"] == entry["source"]
    assert cfg["reduced"] == entry["reduced"]
    for k in entry["reduced"]:
        assert not WIDTHS.search(k), k
    assert cfg["deployment"] and cfg["assumed"]
    # the keys its family reads, and the family's program configuration
    fam = common.family(cfg, ROOT)
    for k in fam.CONFIG_KEYS:
        assert k in cfg
    assert fam.program_config(cfg).num_layers == cfg["num_hidden_layers"]
    # published widths: the family states its sources' sizes (a file
    # whose source it does not state fails here); a key the file cuts is
    # in ``reduced``, and its ``published`` block keeps the value
    published = fam.PUBLISHED[cfg["source"]]
    assert set(entry["reduced"]) <= set(published)
    for k, v in cfg.items():  # every width the file gives
        if WIDTHS.search(k) and isinstance(v, (int, float)):
            assert k in published, k
    for k, v in published.items():
        held = cfg["published"][k] if k in entry["reduced"] else cfg[k]
        assert held == v, k


def test_unseen_config_and_traffic_are_picked_up(tmp_path):
    """A cell whose configuration and traffic files the harness has never
    seen runs from its files alone."""
    bench = json.loads(json.dumps(BENCH))
    cfg = common.load_json(os.path.join(ROOT, "bench", "configs",
                                        "olmo-1b-dsgd-m4.json"))
    cfg.update(name="new-model", num_hidden_layers=2)
    traffic = common.load_json(os.path.join(ROOT, "bench", "traffic",
                                            "dsgd-allreduce.json"))
    traffic["local_steps"] = 3
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "bench" / "configs" / "new-model.json").write_text(
        json.dumps(cfg))
    (tmp_path / "bench" / "traffic" / "new-mix.json").write_text(
        json.dumps(traffic))
    bench["configs"].append({"name": "new-model", "source": "x",
                             "file": "bench/configs/new-model.json",
                             "reduced": [], "why": "x"})
    bench["workloads"].append({"name": "new-model.new-mix",
                               "config": "new-model", "traffic": "new-mix",
                               "chips": 1, "why": "x"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    w, c, t, _ = common.find_cell("new-model.new-mix", str(tmp_path))
    assert c["num_hidden_layers"] == 2 and t["local_steps"] == 3
    assert w["traffic"] == "new-mix"
    with pytest.raises(SystemExit):
        common.find_cell("no-such-cell", str(tmp_path))


def test_peaks_table():
    p = common.peaks("TPU v5 lite", ROOT)
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(SystemExit):
        common.peaks("cpu", ROOT)


def test_judge_and_seed_key():
    import jax
    ok, table = common.judge([("a", 1.0, 2.0), ("n", 300, 200, "min")])
    assert ok and table["n"]["at_least"]
    assert not common.judge([("a", float("nan"), 2.0)])[0]
    assert not common.judge([("a", None, 2.0)])[0]
    k1 = common.seed_key(jax, 2 ** 40 + 3, 1)
    k2 = common.seed_key(jax, 3, 1)
    assert not (k1 == k2).all()
