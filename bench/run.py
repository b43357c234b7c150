"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

The cell, its configuration file and its traffic mix are found by the
names in ``BENCHMARK.json``; the configuration's model family in
``bench/models/<model_type>.py``; its limits for ``correct`` in
``bench/limits/<workload>.json``. The run stops with a non-zero exit
code, and prints no result, unless JAX finds TPUs, as many as the cell
asks for. Set-up (making the weights and the traffic from the seed,
loading or compiling every program the window runs) is timed from the
start of the process as ``setup_s``; then the window runs for
``--seconds``; then the outputs of the timed path are compared with the
plain reference (``bench/reference.py`` and the family's forward pass).
With ``--trace 1`` the window runs under the profiler and the line
carries the per-layer metrics instead of the end-to-end ones, each read
by ``bench/metrics/<name>.py``. A reader may declare the labels it reads
from the trace: ``SCOPES`` ({label: [named-scope substring, ...]}) and
``MODULES`` ({label: [compiled program name, ...]}); the trace is reduced
by the labels of every reader the cell reports, and two readers that give
one label different keys stop the run before JAX starts.

The last line of stdout is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics``, ``device`` (and with ``--trace 1`` the device's
``busy_s`` and ``window_s``, and ``breakdown``), and last ``checks``:
each compared number with its limit, also printed on stderr.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import common  # noqa: E402


def main(argv=None, *, t_start=T_START, require_tpu=True,
         root=common.ROOT):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)

    cell, cfg, traffic, bench = common.find_cell(args.workload, root)
    limits = common.load_json(os.path.join(
        root, "bench", "limits", args.workload + ".json"))["limits"]
    per_layer = common.cell_metrics(bench, cell["name"], "per_layer")
    # the trace labels the cell's readers declare, checked before JAX
    # takes a chip
    declared = {attr: common.declarations(per_layer, attr)
                for attr in ("SCOPES", "MODULES")}
    import jax
    if require_tpu:
        devs = common.require_chips(jax, cell["chips"])
    else:
        devs = jax.devices()[:cell["chips"]]
    common.use_compile_cache(jax)
    peaks = common.peaks(devs[0].device_kind) if require_tpu else None
    from bench import program  # noqa: F401  (puts src/ on the path)
    if traffic["kind"] == "train":
        from bench import train as driver
    else:
        from bench import serve as driver
    result, checks = driver.run(
        jax, cell, cfg, traffic, limits, seed=args.seed,
        seconds=args.seconds, trace=bool(args.trace), t_start=t_start,
        devs=devs, peaks=peaks, per_layer=per_layer, declared=declared)
    common.emit(result, checks)
    return result


if __name__ == "__main__":
    main()
