"""What every cell of the benchmark shares: finding a cell's files by the
names in ``BENCHMARK.json`` (its model family's module too), the device
check, the table of peaks, the compile cache, seeds, and the result line.

Nothing here imports the program (``src/repro``); the drivers in
``bench/train.py`` and ``bench/serve.py`` do.
"""
from __future__ import annotations

import gc
import glob
import importlib.util
import json
import math
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
# fixed paths inside the checkout (listed in .gitignore): the compile cache
# key includes its directory, so it never moves
CACHE_DIR = os.path.join(ROOT, ".jax_cache")
WORK_DIR = os.path.join(ROOT, ".bench_work")


class NoChip(SystemExit):
    """No accelerator, or fewer chips than the cell asks for."""


# ----------------------------------------------------------------- files


def load_json(path):
    with open(path) as f:
        return json.load(f)


def load_benchmark(root=ROOT):
    return load_json(os.path.join(root, "BENCHMARK.json"))


def find_cell(name, root=ROOT):
    """(cell, config file, traffic file, benchmark) of the workload
    ``name``: the configuration is the file its entry names, the traffic
    mix is ``bench/traffic/<traffic>.json``."""
    bench = load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg = load_json(os.path.join(root, configs[cell["config"]]["file"]))
    traffic = load_json(os.path.join(root, "bench", "traffic",
                                     cell["traffic"] + ".json"))
    return cell, cfg, traffic, bench


def cell_metrics(bench, cell_name, kind):
    """The metric entries of ``kind`` ('end_to_end' or 'per_layer') that
    the cell reports: those without a ``workloads`` key, and those that
    list it."""
    return [m for m in bench[kind]
            if cell_name in m.get("workloads", [cell_name])]


def _load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_metric_reader(name, root=ROOT):
    """The module ``bench/metrics/<name>.py`` of one per-layer metric."""
    return _load_module(
        os.path.join(root, "bench", "metrics", name + ".py"),
        "bench_metric_" + name.replace(".", "_"))


_FAMILIES = {}


def family(cfg, root=ROOT):
    """The module ``bench/models/<model_type>.py`` of a configuration
    file's model family: everything that depends on the architecture
    (the program's configuration and parameter tree, the reference's
    forward pass, the counts)."""
    name = cfg["model_type"]
    path = os.path.join(root, "bench", "models", name + ".py")
    if path not in _FAMILIES:
        if not os.path.exists(path):
            raise SystemExit(f"no family module {path} for model_type "
                             f"{name!r}")
        _FAMILIES[path] = _load_module(
            path, "bench_family_" + name.replace(".", "_").replace("-", "_"))
    return _FAMILIES[path]


def declarations(per_layer, attr, root=ROOT):
    """{label: [key, ...]} that the readers of ``per_layer`` declare under
    ``attr``: ``SCOPES`` (a label for the device time of ops whose
    ``jax.named_scope`` path holds one of the keys) or ``MODULES`` (a
    label for the device time inside runs of the compiled programs
    named). A reader may share a label with another only with the same
    keys; two that differ stop the run, naming both."""
    out, owner = {}, {}
    for m in per_layer:
        mod = load_metric_reader(m["name"], root)
        for label, keys in getattr(mod, attr, {}).items():
            keys = list(keys)
            if label in out and out[label] != keys:
                raise SystemExit(
                    f"bench: the readers {owner[label]} and {m['name']} "
                    f"declare the {attr} label {label!r} with different "
                    f"keys ({out[label]} and {keys}); nothing was run")
            out[label], owner[label] = keys, owner.get(label, m["name"])
    return out


def read_metrics(per_layer, ctx):
    """{name: {value, unit}} of the per-layer metrics whose reader finds
    something to read in ``ctx``; a reader that finds nothing returns
    None and the metric is left out."""
    out = {}
    for m in per_layer:
        v = load_metric_reader(m["name"]).read(ctx)
        if v is not None and math.isfinite(v):
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def peaks(device_kind, root=ROOT):
    """The device's peaks from ``bench/peaks.json``. A kind that is not in
    the table is an error, never a default."""
    table = load_json(os.path.join(root, "bench", "peaks.json"))
    if device_kind not in table["devices"]:
        raise SystemExit(f"device kind {device_kind!r} is not in "
                         f"bench/peaks.json ({sorted(table['devices'])})")
    return table["devices"][device_kind]


# ---------------------------------------------------------------- device


def require_chips(jax, chips):
    """Stop, printing no result, unless JAX's devices are TPUs and there
    are at least ``chips`` of them. Returns the devices the cell uses."""
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"bench: JAX found no TPU (first device: "
                     f"{devs[0].platform}); nothing was run")
    if len(devs) < chips:
        raise NoChip(f"bench: the cell needs {chips} chips, JAX found "
                     f"{len(devs)}; nothing was run")
    return devs[:chips]


def memory_peaks(devs, key="peak_bytes_in_use"):
    """Each device's ``key`` of its memory stats so far (0 where the
    backend reports none)."""
    return [int((d.memory_stats() or {}).get(key, 0)) for d in devs]


def print_memory_peaks(devs, when):
    """Each chip's peak of buffers in use, and of memory reserved beside
    them. Which of the two holds a compiled program's temporaries is the
    runtime's affair; both are printed so that a run shows it."""
    peaks = memory_peaks(devs)
    print(f"memory peak per chip {when}: {peaks} B (fullest {max(peaks)} "
          f"B); reserved {memory_peaks(devs, 'peak_bytes_reserved')} B",
          flush=True)


def device_info(devs):
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": max(memory_peaks(devs))}


def use_compile_cache(jax):
    """JAX's persistent compile cache: ``JAX_COMPILATION_CACHE_DIR`` when
    it is set, else ``.jax_cache`` in the checkout. Every program is
    cached, however quick its compile, so a second run compiles nothing."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def seed_key(jax, seed, stream):
    """A PRNG key for one named stream of ``seed`` (any non-negative
    integer, wider than 32 bits too)."""
    key = jax.random.PRNGKey(stream)
    seed = int(seed)
    while True:
        key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
        seed >>= 31
        if not seed:
            return key


def work_dir(*parts):
    path = os.path.join(WORK_DIR, *parts)
    os.makedirs(path, exist_ok=True)
    return path


# ---------------------------------------------------------------- window


# JAX's duration events for a trace and for a compile (a load from the
# persistent compile cache is a compile request too)
_COMPILE_EVENTS = {"/jax/core/compile/jaxpr_trace_duration": "traces",
                   "/jax/core/compile/backend_compile_duration": "compiles"}
_WATCHES = []


def _on_duration(event, secs, **_):
    kind = _COMPILE_EVENTS.get(event)
    if kind:
        for w in _WATCHES:
            w.add(kind, secs)


def _on_gc(phase, info):
    for w in _WATCHES:
        if phase == "start":
            w.gc_start = time.perf_counter()
        elif w.gc_start is not None:
            w.add("gc", time.perf_counter() - w.gc_start)
            w.gc_start = None


class WindowWatch:
    """Counts what the host did inside a window besides the work: JAX
    traces and compiles, and the garbage collector's passes, each with its
    seconds and its longest. With ``freeze`` the heap built in set-up is
    collected and frozen when the watch is made (set-up), so the
    collector's passes in the window do not walk it."""

    def __init__(self, jax, freeze=False):
        if _on_gc not in gc.callbacks:
            jax.monitoring.register_event_duration_secs_listener(_on_duration)
            gc.callbacks.append(_on_gc)
        self.freeze = freeze
        if freeze:
            gc.collect()
            gc.freeze()
        self.stats = {k: [0, 0.0, 0.0] for k in ("traces", "compiles", "gc")}
        self.gc_start = None

    def add(self, kind, secs):
        st = self.stats[kind]
        st[0] += 1
        st[1] += secs
        st[2] = max(st[2], secs)

    def __enter__(self):
        _WATCHES.append(self)
        return self

    def __exit__(self, *exc):
        _WATCHES.remove(self)
        if self.freeze:
            gc.unfreeze()

    def line(self):
        return "in the window: " + "; ".join(
            f"{k} {n} ({s:.3f} s, longest {mx:.3f} s)"
            for k, (n, s, mx) in self.stats.items())


# ---------------------------------------------------------------- result


def quantile(values, q):
    """The q-quantile of ``values`` by linear interpolation between order
    statistics (numpy's default)."""
    xs = sorted(values)
    if not xs:
        return float("nan")
    pos = q * (len(xs) - 1)
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def judge(checks):
    """``checks``: [(name, value, limit)] or [(name, value, limit, "min")].
    A number passes when it is at most its limit (at least, for "min");
    a missing or NaN value fails. Returns (correct, table)."""
    ok, table = True, {}
    for name, v, lim, *rule in checks:
        at_least = rule == ["min"]
        passed = v is not None and (v >= lim if at_least else v <= lim)
        ok = ok and bool(passed)
        table[name] = {"value": v, "limit": lim}
        if at_least:
            table[name]["at_least"] = True
    return ok, table


def emit(result, checks_table):
    """The last lines of a run: the compared numbers on stderr, then the
    result line on stdout with those numbers under ``checks``, last."""
    for name, c in checks_table.items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    line = dict(result)
    line["checks"] = checks_table
    print(json.dumps(line), flush=True)


def clear_dir(path):
    shutil.rmtree(path, ignore_errors=True)


def newest_trace(logdir):
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise RuntimeError(f"no profiler trace under {logdir}")
    return paths[-1]
