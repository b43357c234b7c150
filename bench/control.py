"""Readings that the limits for ``correct`` are set from, on the chip at a
cell's own size, in one process:

- sound runs of the program on ``--sound`` seeds (the lower readings);
- the control, the reference one step below the precision the
  configuration states, in the program's place (bfloat16 for training;
  float8 operands in the weight matmuls for serving, with the bfloat16
  reference read beside it as ``control_bf16``), on ``--control`` seeds
  (the upper readings);
- the faults a cell can have, planted in the program, on ``--faults``
  seeds.

    python3 bench/control.py --workload <name> --seed <first> \\
        --sound 12 --control 3 --faults 3

Each reading prints as one JSON line; the last line is the summary: per
number, the largest sound reading and the smallest control and fault
readings. The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import common  # noqa: E402


# ------------------------------------------------------------ faults


def half_batch(loss_fn):
    """The loss over the first half of each agent's batch only."""
    def wrapped(params, batch, rng=None):
        half = {k: v[:v.shape[0] // 2] for k, v in batch.items()}
        return loss_fn(params, half, rng)
    return wrapped


def no_mix(jnp):
    """A gossip mix that exchanges nothing: every agent keeps its row."""
    def mix_dense_mean(panel, W, **kw):
        mean = {k: jnp.mean(x.astype(jnp.float32), axis=0)
                for k, x in panel.items()}
        return panel, mean, None
    return mix_dense_mean


TRAIN_FAULTS = ("half_batch", "no_mix")


def train_readings(jax, cfg, traffic, seed, fault=None, ref32=None,
                   chips=1):
    """(numbers, reference readings, the reference's inputs) of one seed:
    the program (with ``fault`` planted, if any) on ``chips`` chips
    against the float32 reference placed as the program is."""
    from bench import train
    from repro.core import panel as panel_mod
    saved = panel_mod.mix_dense_mean
    try:
        if fault == "no_mix":
            panel_mod.mix_dense_mean = no_mix(jax.numpy)
        tr, prog = train.setup_and_check_calls(
            jax, cfg, traffic, seed,
            loss_wrap=half_batch if fault == "half_batch" else None,
            chips=chips)
    finally:
        panel_mod.mix_dense_mean = saved
    K = traffic["check_calls"]
    inputs = (tr.k_w, tr.pool[:K], tr.Ws_host[:K])
    mesh = tr.mesh
    tr.free()
    del tr
    gc.collect()
    if ref32 is None:
        ref32 = train.reference_run(jax, cfg, traffic, inputs, K,
                                    jax.numpy.float32, mesh=mesh)
    return train.compare(prog, ref32), ref32, inputs


def train_control(jax, cfg, traffic, inputs, ref32, chips=1):
    from bench import train
    ctl = train.reference_run(
        jax, cfg, traffic, inputs, traffic["check_calls"],
        jax.numpy.bfloat16,
        mesh=train.placement(chips, cfg["job"]["agents"]))
    return train.compare(ctl, ref32)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--sound", type=int, default=12)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--faults", type=int, default=3)
    args = ap.parse_args(argv)
    cell, cfg, traffic, _ = common.find_cell(args.workload)
    import jax
    common.require_chips(jax, cell["chips"])
    common.use_compile_cache(jax)
    from bench import program  # noqa: F401  (puts src/ on the path)
    rows = []

    def out(kind, seed, nums):
        row = {"kind": kind, "seed": seed, **nums}
        rows.append(row)
        print(json.dumps(row), flush=True)

    n = max(args.sound, args.control, args.faults)
    chips = cell["chips"]
    for i in range(n):
        seed = args.seed + i
        if traffic["kind"] == "train":
            nums, ref32, inputs = train_readings(jax, cfg, traffic, seed,
                                                 chips=chips)
            if i < args.sound:
                out("sound", seed, nums)
            if i < args.control:
                out("control", seed, train_control(jax, cfg, traffic,
                                                   inputs, ref32, chips))
            if i < args.faults:
                for f in TRAIN_FAULTS:
                    nums, _, _ = train_readings(jax, cfg, traffic, seed,
                                                fault=f, ref32=ref32,
                                                chips=chips)
                    out(f, seed, nums)
            del ref32, inputs
        else:
            from bench import serve
            for kind, nums in serve.control_readings(
                    jax, cfg, traffic, seed, sound=i < args.sound,
                    control=i < args.control, faults=i < args.faults):
                out(kind, seed, nums)
        gc.collect()
    summary = {}
    for kind in sorted({r["kind"] for r in rows}):
        sel = [r for r in rows if r["kind"] == kind]
        keys = [k for k in sel[0] if k not in ("kind", "seed")]
        agg = max if kind == "sound" else min
        summary[kind] = {k: agg(r[k] for r in sel) for k in keys}
        summary[kind]["seeds"] = len(sel)
    print(json.dumps({"summary": summary}), flush=True)


if __name__ == "__main__":
    main()
