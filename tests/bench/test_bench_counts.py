"""The benchmark's FLOP and byte counts against hand-worked values for
olmo-1b at 1, 2 and 16 layers."""
import json
import os

import pytest

from bench import common, counts

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cfg(name):
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        return json.load(f)


# per layer: q, k, v, o at 2048 x 2048 (4 * 4,194,304) and the SwiGLU
# MLP 3 x 2048 x 8192 (50,331,648); the tied head 2048 x 50432
LAYER = 4 * 2048 * 2048 + 3 * 2048 * 8192
HEAD = 2048 * 50432


@pytest.mark.parametrize("name,layers,params", [
    ("olmo-1b-dsgd-m4", 1, 170_393_600),
    ("olmo-1b-serve", 16, 1_177_026_560),
])
def test_params(name, layers, params):
    cfg = _cfg(name)
    assert cfg["num_hidden_layers"] == layers
    olmo = common.family(cfg)
    assert olmo.layer_matmul_params(cfg) == LAYER == 67_108_864
    assert counts.params(cfg) == layers * LAYER + HEAD == params


def test_params_two_layers():
    """Two layers, as an agent with a chip of its own holds them."""
    cfg = dict(_cfg("olmo-1b-dsgd-m4"), num_hidden_layers=2)
    assert counts.params(cfg) == 2 * LAYER + HEAD == 237_502_464


def test_train_flops_per_token_one_layer():
    cfg = _cfg("olmo-1b-dsgd-m4")
    # 6 N, and causal attention: 3 x 4 x 16 heads x 128 x (512 + 1) / 2
    want = 6 * 170_393_600 + 3 * 4 * 16 * 128 * 256.5
    assert counts.train_flops_per_token(cfg, 512) == pytest.approx(want)
    # 4 agents x 4 x 512 tokens: 8.43 TFLOP per local step
    assert 4 * 2048 * counts.train_flops_per_token(cfg, 512) == \
        pytest.approx(8.4263e12, rel=1e-4)


def test_adamw_bytes_bf16_moments():
    # 4 + 4 read and 4 written for params and grads, 4 x 2 for moments
    assert counts.adamw_bytes(1, moment="bfloat16") == 20
    assert counts.adamw_bytes(4 * 170_393_600, moment="bfloat16") == \
        13_631_488_000
    assert counts.adamw_bytes(1) == 28


def test_serving_counts_sixteen_layers():
    cfg = _cfg("olmo-1b-serve")
    assert counts.weight_bytes(cfg) == 4_708_106_240
    # K and V of 16 layers x 16 heads x 128, f32
    assert counts.kv_bytes_per_position(cfg) == 2 * 16 * 16 * 128 * 4
    assert counts.decode_step_bytes(cfg, 100) == 4_708_106_240 + 100 * 262_144
    assert counts.decode_flops(cfg, 10) == \
        2 * 1_177_026_560 + 4 * 2048 * 10 * 16
    n = 64
    assert counts.prefill_flops(cfg, n) == \
        2 * 1_177_026_560 * n + 4 * 2048 * 16 * n * (n + 1) / 2
