"""The serving cell at test widths on the CPU: a sound run is correct; the
control (the reference with float8 operands in its weight matmuls, in
the program's place) reads above the limits; and a run with the timed path broken underneath comes out
not correct, once for each fault a serving cell can have."""
import jax
import pytest

from bench import common, run, serve

CELLS = [w["name"] for w in common.load_benchmark()["workloads"]
         if w["traffic"].startswith("serve")]


def _run(root, cell):
    return run.main(["--workload", cell, "--seed", str(2 ** 31 + 9),
                     "--seconds", "2", "--trace", "0"],
                    require_tpu=False, root=root)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_root, cell):
    res = _run(tiny_root, cell)
    assert res["correct"] is True
    m = res["metrics"]
    assert m["serve_tokens_per_s"]["value"] > 0
    assert m["serve_itl_p95_ms"]["value"] > 0
    assert "serve_ttft_p50_ms" not in m
    assert res["attempted"] > 10


def test_control_and_faults_read_above_the_limit(tiny_root):
    cell, cfg, traffic, _ = common.find_cell(CELLS[0], tiny_root)
    lim = common.load_json(
        f"{tiny_root}/bench/limits/{CELLS[0]}.json")["limits"]
    got = dict(serve.control_readings(jax, cfg, traffic, 4, sound=True,
                                      control=True, faults=False))
    for k in ("served_gap", "prefill_logit_err"):
        assert got["sound"][k] <= lim[k]
        assert got["control"][k] > lim[k]


def _altered_token(monkeypatch):
    from repro.serving import engine
    real = engine.sample_token
    monkeypatch.setattr(
        engine, "sample_token",
        lambda logits, rng, t=0.0, vocab_size=None:
        (real(logits, rng, t, vocab_size) + 1) % vocab_size)


def _stale_cache(monkeypatch):
    import repro.models
    real = repro.models.build_model
    monkeypatch.setattr(repro.models, "build_model",
                        lambda cfg: serve.stale_cache(real(cfg)))


@pytest.mark.parametrize("fault", [_altered_token, _stale_cache],
                         ids=["altered_token", "stale_cache"])
def test_fault_is_not_correct(tiny_root, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(tiny_root, CELLS[0])
    assert res["correct"] is False
