"""Fixtures of the benchmark's tests: the checkout root on the path, and a
root holding tiny cells (the same files as the real ones, at widths a
test run on the CPU can hold)."""
import copy
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

TINY_TRAIN_LIMITS = {"loss": 1e-4, "grad_norm": 1e-4, "grad_norm_first": 1e-4,
                     "update": 1e-4, "consensus": 1e-4, "nonfinite_losses": 0,
                     "schedule_faults": 0}
TINY_SERVE_LIMITS = {"served_gap": 1e-3, "prefill_logit_err": 1e-3,
                     "wrong_length": 0, "checked_tokens": 10}


def _load(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def _dump(obj, root, *parts):
    path = os.path.join(root, *parts)
    os.makedirs(os.path.dirname(path), exist_ok=True)
    with open(path, "w") as f:
        json.dump(obj, f)


def tiny_config(cfg):
    """``cfg`` at test widths (the real files' keys and structure)."""
    cfg = copy.deepcopy(cfg)
    cfg.update({"hidden_size": 32, "intermediate_size": 64,
                "num_attention_heads": 2, "num_key_value_heads": 2,
                "vocab_size": 250, "padded_vocab_size": 256,
                "max_position_embeddings": 256})
    if "job" in cfg:
        cfg["job"].update({"batch": 2, "seq_len": 16})
    if "serving" in cfg:
        cfg.update({"num_hidden_layers": 2})
        cfg["serving"] = {"slots": 4, "max_len": 64}
    return cfg


def tiny_traffic(traffic):
    traffic = copy.deepcopy(traffic)
    if traffic["kind"] == "serve":
        traffic.update({"rate_per_s": 20.0, "prompt_lengths": [8, 16, 32],
                        "prompt_weights": [0.5, 0.3, 0.2],
                        "output_median": 8, "output_min": 2,
                        "output_max": 16, "check_sample": 16,
                        "check_window_s": 3})
    else:
        traffic["pool_segments"] = 2
    return traffic


@pytest.fixture(scope="session")
def tiny_root(tmp_path_factory):
    """A root with every cell of BENCHMARK.json, each at test widths."""
    root = str(tmp_path_factory.mktemp("tiny_bench"))
    bench = _load("BENCHMARK.json")
    for c in bench["configs"]:
        _dump(tiny_config(_load(c["file"])), root, c["file"])
    for w in bench["workloads"]:
        t = _load("bench", "traffic", w["traffic"] + ".json")
        _dump(tiny_traffic(t), root, "bench", "traffic",
              w["traffic"] + ".json")
        lim = (TINY_SERVE_LIMITS if t["kind"] == "serve"
               else TINY_TRAIN_LIMITS)
        _dump({"limits": lim}, root, "bench", "limits", w["name"] + ".json")
    _dump(bench, root, "BENCHMARK.json")
    return root
