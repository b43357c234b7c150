"""A four-chip training cell on four CPU devices, at test widths: the
one-chip ``allreduce`` cell with f32 moments and one agent per device,
as a cell whose agents each need a chip of their own is placed. The
trainer's check segment against the sharded reference under the
allreduce cell's limits; the sharded reference against the one-device
reference; and a run with the timed path broken underneath comes out not
correct, once for each fault such a cell can have (the state returned
unchanged, half of each batch left out, the exchange between chips left
out)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
BASE = "dsgd-m4.allreduce"
CELL = "x4.allreduce"

SCRIPT = r'''
import contextlib, io, json, os, sys
sys.path.insert(0, ROOT); sys.path.insert(0, HERE)
import jax
import numpy as np
import conftest as cf
from bench import common, control, run, train

root = TMP
bench = cf._load("BENCHMARK.json")
base = [w for w in bench["workloads"] if w["name"] == BASE][0]
conf = [c for c in bench["configs"] if c["name"] == base["config"]][0]
conf = dict(conf, name="x4", file="bench/configs/x4.json")
tiny = cf.tiny_config(cf._load("bench", "configs", base["config"] + ".json"))
tiny["name"] = "x4"
# f32 moments; the reference takes each agent's batch in two blocks of
# rows, as it would take a batch of 16 in four
tiny["job"].update(batch=2 * train.REFERENCE_ROWS, moments="f32")
cf._dump(tiny, root, conf["file"])
cf._dump(cf.tiny_traffic(cf._load("bench", "traffic",
                                  base["traffic"] + ".json")),
         root, "bench", "traffic", base["traffic"] + ".json")
limits = cf._load("bench", "limits", BASE + ".json")
cf._dump(limits, root, "bench", "limits", CELL + ".json")
bench["configs"].append(conf)
bench["workloads"].append(dict(base, name=CELL, config="x4", chips=4))
cf._dump(bench, root, "BENCHMARK.json")
out = {"devices": len(jax.devices())}


def go():
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        run.main(["--workload", CELL, "--seed", str(2 ** 32 + 77),
                  "--seconds", "1", "--trace", "0"],
                 require_tpu=False, root=root)
    lines = buf.getvalue().strip().splitlines()
    res = json.loads(lines[-1])
    return {"correct": res["correct"], "count": res["device"]["count"],
            "checks": {k: c["value"] for k, c in res["checks"].items()},
            "window": [x for x in lines if x.startswith("in the window")]}


out["sound"] = go()

# the trainer's check segment against the sharded reference, and the
# sharded reference against the one-device reference, on one seed
_, cfg, traffic, _ = common.find_cell(CELL, root)
tr, prog = train.setup_and_check_calls(jax, cfg, traffic, 7, chips=4)
out["state_sharded"] = all(
    len(x.sharding.device_set) == 4
    for x in jax.tree.leaves(tr.state["panel"]))
inputs = (tr.k_w, tr.pool[:1], tr.Ws_host[:1])
mesh = tr.mesh
tr.free()
f32 = jax.numpy.float32
sharded = train.reference_run(jax, cfg, traffic, inputs, 1, f32, mesh=mesh)
dev0 = jax.devices()[0]
one = train.reference_run(
    jax, cfg, traffic,
    (inputs[0], [jax.device_put(b, dev0) for b in inputs[1]], inputs[2]),
    1, f32)
out["against_sharded"] = train.compare(prog, sharded)
out["reference_gap"] = {
    k: float(np.max(np.abs(sharded[k] - one[k])
                    / np.maximum(np.abs(one[k]), 1e-30)))
    for k in ("loss", "grad_norm", "consensus", "deltas", "first_grad")}
out["reference_equal"] = {k: bool(np.array_equal(sharded[k], one[k]))
                          for k in ("loss", "consensus", "deltas")}

# the faults
from repro.core import dsgd, panel
real_segment, real_mix = dsgd.make_panel_segment, panel.mix_dense_mean


def state_unchanged(*a, **kw):
    seg = real_segment(*a, **dict(kw, donate=False))
    return jax.jit(lambda state, *args: (state, seg(state, *args)[1]))


def half_batch(loss_fn, *a, **kw):
    return real_segment(control.half_batch(loss_fn), *a, **kw)


for name in ("state_unchanged", "half_batch", "no_mix"):
    if name == "state_unchanged":
        dsgd.make_panel_segment = state_unchanged
    elif name == "half_batch":
        dsgd.make_panel_segment = half_batch
    else:
        panel.mix_dense_mean = control.no_mix(jax.numpy)
    try:
        out[name] = go()
    finally:
        dsgd.make_panel_segment, panel.mix_dense_mean = (real_segment,
                                                         real_mix)
print(json.dumps(out))
'''


@pytest.fixture(scope="module")
def x4(tmp_path_factory):
    """The script's readings, in a process whose CPU shows four devices
    (JAX fixes the count when it starts)."""
    tmp = str(tmp_path_factory.mktemp("x4"))
    script = (f"ROOT = {ROOT!r}\nHERE = {HERE!r}\nTMP = {tmp!r}\n"
              f"BASE = {BASE!r}\nCELL = {CELL!r}\n" + SCRIPT)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS=(os.environ.get("XLA_FLAGS", "") + " --xla_force_"
                          "host_platform_device_count=4").strip(),
               PYTHONPATH=os.path.join(ROOT, "src"))
    p = subprocess.run([sys.executable, "-c", script], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.multidevice
def test_sound_run_on_four_devices_is_correct(x4):
    assert x4["devices"] == 4
    assert x4["sound"]["correct"] is True
    assert x4["sound"]["count"] == 4
    assert x4["state_sharded"] is True
    # the check call ran the window's program: nothing compiles after it
    assert len(x4["sound"]["window"]) == 1
    assert "compiles 0 " in x4["sound"]["window"][0]


@pytest.mark.multidevice
def test_check_segment_agrees_with_sharded_reference_within_limits(x4):
    with open(os.path.join(ROOT, "bench", "limits", BASE + ".json")) as f:
        limits = json.load(f)["limits"]
    nums = x4["against_sharded"]
    for k, lim in limits.items():
        if k in nums:  # nonfinite_losses counts the window's losses
            assert nums[k] <= lim, k
    assert set(limits) - set(nums) == {"nonfinite_losses"}


@pytest.mark.multidevice
def test_sharded_reference_equals_one_device_reference(x4):
    """Every agent's losses and weights to the bit; the gradient norms,
    sums over agents that cross devices in another order, to rounding."""
    assert all(x4["reference_equal"].values()), x4["reference_gap"]
    for k in ("grad_norm", "first_grad"):
        assert x4["reference_gap"][k] <= 1e-5, x4["reference_gap"]


@pytest.mark.multidevice
@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "no_mix"])
def test_fault_on_four_devices_is_not_correct(x4, fault):
    assert x4[fault]["count"] == 4
    assert x4[fault]["correct"] is False, x4[fault]["checks"]


@pytest.mark.parametrize("chips,agents", [(2, 4), (4, 8), (4, 2)])
def test_placement_is_one_chip_or_one_agent_per_chip(chips, agents):
    from bench import train
    assert train.placement(1, agents) is None
    with pytest.raises(ValueError):
        train.placement(chips, agents)
