"""One-chip training cells at test widths on the CPU: a sound run is
correct; the control (the reference in bfloat16 in the program's place)
reads above every limit-worthy sound reading; and a run with the timed
path broken underneath comes out not correct, once for each fault a
training cell on one chip can have."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import common, control, run, train

# the one-chip cells (the four-chip cell's are in test_bench_x4.py)
CELLS = [w["name"] for w in common.load_benchmark()["workloads"]
         if w["traffic"].startswith("dsgd") and w["chips"] == 1]


def _run(root, cell):
    return run.main(["--workload", cell, "--seed", str(2 ** 32 + 5),
                     "--seconds", "1", "--trace", "0"],
                    require_tpu=False, root=root)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tiny_root, cell):
    res = _run(tiny_root, cell)
    assert res["correct"] is True
    assert res["metrics"]["train_tokens_per_s"]["value"] > 0
    assert res["device"]["count"] == 1


def test_control_reads_above_sound(tiny_root):
    cell, cfg, traffic, _ = common.find_cell(CELLS[0], tiny_root)
    sound, ref32, inputs = control.train_readings(jax, cfg, traffic, 11)
    ctl = control.train_control(jax, cfg, traffic, inputs, ref32)
    limits = common.load_json(f"{tiny_root}/bench/limits/{CELLS[0]}.json")
    assert any(ctl[k] > 3 * sound[k] for k in sound)
    assert any(ctl[k] > limits["limits"][k] for k in sound)


def _state_unchanged(monkeypatch):
    from repro.core import dsgd
    real = dsgd.make_panel_segment

    def make(*a, **kw):
        seg = real(*a, **dict(kw, donate=False))

        def segment(state, *args):
            _, mets = seg(state, *args)
            return state, mets
        return segment
    monkeypatch.setattr(dsgd, "make_panel_segment", make)


def _half_batch(monkeypatch):
    from repro.core import dsgd
    real = dsgd.make_panel_segment
    monkeypatch.setattr(dsgd, "make_panel_segment",
                        lambda loss_fn, *a, **kw: real(
                            control.half_batch(loss_fn), *a, **kw))


def _no_mix(monkeypatch):
    from repro.core import panel
    monkeypatch.setattr(panel, "mix_dense_mean", control.no_mix(jnp))


def _no_merge(monkeypatch):
    """Global rounds whose matrix exchanges nothing: the program and the
    reference would both follow it; only the schedule's form catches it."""
    from repro.core import topology
    monkeypatch.setattr(topology, "fully_connected",
                        lambda m: np.eye(m))


@pytest.mark.parametrize("fault", [_state_unchanged, _half_batch, _no_mix,
                                   _no_merge],
                         ids=["state_unchanged", "half_batch", "no_mix",
                              "no_merge"])
def test_fault_is_not_correct(tiny_root, monkeypatch, fault):
    fault(monkeypatch)
    res = _run(tiny_root, CELLS[0])
    assert res["correct"] is False


def test_compare_reads_one_for_an_unchanged_state(tiny_root):
    cell, cfg, traffic, _ = common.find_cell(CELLS[0], tiny_root)
    nums, ref32, _ = control.train_readings(jax, cfg, traffic, 3)
    prog = dict(ref32, deltas=0 * ref32["deltas"])
    assert train.compare(prog, ref32)["update"] == pytest.approx(1.0)


def test_compare_first_round_gaps():
    refr = {"loss": np.array([10.0, 9.0, 8.0]),
            "grad_norm": np.array([2.0, 1.0, 1.5]),
            "consensus": np.array([1.0, 2.0, 0.0]),
            "deltas": np.ones((2, 3)), "first_grad": np.ones(3)}
    prog = dict(refr, loss=np.array([10.01, 9.0, 8.4]),
                grad_norm=np.array([2.002, 1.0, 1.2]))
    nums = train.compare(prog, refr)
    assert nums["loss_first"] == pytest.approx(1e-3)
    assert nums["loss"] == pytest.approx(0.05)
    assert nums["grad_norm_first"] == pytest.approx(1e-3)
    assert nums["grad_norm"] == pytest.approx(0.2)


_PAIR = np.array([[.5, .5, 0, 0], [.5, .5, 0, 0], [0, 0, 1, 0], [0, 0, 0, 1]])
_THREE = np.array([[.5, .25, .25, 0], [.25, .5, .25, 0], [.25, .25, .5, 0],
                   [0, 0, 0, 1]])


@pytest.mark.parametrize("W, is_global, t, fault", [
    (np.eye(4), False, 0, False),
    (_PAIR, False, 3, False),
    (np.full((4, 4), .25), True, 15, False),
    (np.full((4, 4), .25), True, 14, True),
    (np.eye(4), False, 15, True),
    (_PAIR, True, 15, True),
    (_PAIR + np.triu(np.full((4, 4), 1e-3), 1), False, 3, True),
    (_THREE, False, 3, True),
], ids=["idle", "pair", "merge", "merge_early", "merge_missing",
        "merge_not_mean", "asymmetric", "three_way"])
def test_round_fault(W, is_global, t, fault):
    assert train.round_fault(W.astype(np.float32), is_global, t, 16) is fault
