"""Reduction of a profiler trace to the numbers the per-layer metrics read.

Two stages. :func:`extract` reads an ``.xplane.pb`` with
``jax.profiler.ProfileData`` into plain lists (JSON-able, so a small
recorded trace can stand in the tests): per device, its op events
``[name, start_ns, end_ns, scope, opcode]``; on the host, the spans
``[name, start_ns, end_ns]``. :func:`reduce` turns those into busy time,
the window, time per scope, collective time not hidden under compute,
and the longest idle gaps with the host span each falls in.

A device op's ``scope`` is the ``jax.named_scope`` path the program gave
it (``.../dsgd.local_grad/...``), read from the compiled module's text:
the trace names each op by its HLO instruction only.
"""
from __future__ import annotations

import bisect
import re

WINDOW_SPAN = "bench.window"
COLLECTIVE = re.compile(r"^(all-gather|all-reduce|reduce-scatter|"
                        r"collective-permute|all-to-all)")
CONTROL = ("while", "conditional", "call")
_EVENT = re.compile(r"^%(\S+) = .*? ([a-z][a-z0-9\-]*)\(")
_METADATA = re.compile(
    r"^\s*(?:ROOT )?%(\S+) = .*?metadata=\{op_name=\"([^\"]*)\"")


def op_names(hlo_text):
    """{instruction: op_name} of a compiled module's text: the
    ``jax.named_scope`` path of each instruction (a fusion's is its
    root's)."""
    out = {}
    for line in hlo_text.splitlines():
        mt = _METADATA.match(line)
        if mt:
            out[mt.group(1)] = mt.group(2)
    return out


def extract(path, names):
    """``{"devices": {plane: [[op, t0, t1, scope, opcode], ...]},
    "modules": {plane: [[module, t0, t1], ...]}, "host": [[name, t0, t1],
    ...]}`` from one trace file. Device planes are
    ``/device:TPU:<n>``; their ops are the events of the line ``XLA
    Ops``, each named by its HLO instruction, whose scope comes from
    ``names`` (:func:`op_names` of the modules the window ran). Control
    flow (while, conditional, call) is left out: the ops inside it are
    the work."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices, modules, host = {}, {}, []
    for plane in pd.planes:
        if re.fullmatch(r"/device:TPU:\d+", plane.name):
            ops, mods = [], []
            for line in plane.lines:
                if line.name == "XLA Modules":
                    mods.extend([e.name.split("(")[0], int(e.start_ns),
                                 int(e.start_ns + e.duration_ns)]
                                for e in line.events)
                if line.name != "XLA Ops":
                    continue
                for e in line.events:
                    mt = _EVENT.match(e.name)
                    op, code = (mt.group(1), mt.group(2)) if mt else \
                        (e.name, "")
                    if code in CONTROL:
                        continue
                    t0 = int(e.start_ns)
                    ops.append([op, t0, t0 + int(e.duration_ns),
                                names.get(op, ""), code])
            devices[plane.name] = ops
            modules[plane.name] = mods
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.duration_ns > 0:
                        host.append([e.name, int(e.start_ns),
                                     int(e.start_ns + e.duration_ns)])
    return {"devices": devices, "modules": modules, "host": host}


# -------------------------------------------------------- interval sets


def union(intervals):
    """Sorted, disjoint [t0, t1] pairs covering ``intervals``."""
    out = []
    for a, b in sorted((a, b) for a, b in intervals if b > a):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def clip(intervals, w0, w1):
    return [[max(a, w0), min(b, w1)] for a, b in intervals
            if min(b, w1) > max(a, w0)]


def total(intervals):
    return sum(b - a for a, b in union(intervals))


def minus(intervals, cover):
    """The parts of ``intervals`` that ``cover`` leaves uncovered."""
    cover = union(cover)
    out = []
    for a, b in union(intervals):
        t = a
        for c0, c1 in cover:
            if c1 <= t or c0 >= b:
                continue
            if c0 > t:
                out.append([t, c0])
            t = max(t, c1)
            if t >= b:
                break
        if t < b:
            out.append([t, b])
    return out


def gaps(busy, w0, w1):
    """Idle intervals of [w0, w1] outside ``busy`` (a union)."""
    return minus([[w0, w1]], busy)


# ------------------------------------------------------------ reduction


def window(ex):
    """[t0, t1] of the host span ``bench.window``; else the span of the
    device ops."""
    spans = [h for h in ex["host"] if h[0] == WINDOW_SPAN]
    if spans:
        return spans[-1][1], spans[-1][2]
    ts = [e for ops in ex["devices"].values() for e in ops]
    return min(e[1] for e in ts), max(e[2] for e in ts)


def reduce(ex, scopes, modules=None, top=10):
    """Per device, within the window: busy ns, ns under each scope prefix
    of ``scopes`` ({label: [substring, ...]}), ns of the ops inside the
    runs of each module of ``modules`` ({label: [module name, ...]}),
    collective ns and the part
    of it not overlapped by other ops. Plus the window, the labels it was
    reduced by, the busiest device ops and the longest idle gaps with the
    host span they fall in."""
    w0, w1 = window(ex)
    per_dev = {}
    op_time = {}
    all_gaps = []
    host = [h for h in ex["host"] if h[0] != WINDOW_SPAN]
    for dev, ops in sorted(ex["devices"].items()):
        ops = [o for o in ops if o[2] > w0 and o[1] < w1]
        iv = clip([[o[1], o[2]] for o in ops], w0, w1)
        busy = union(iv)
        by_scope = {}
        for label, keys in scopes.items():
            sel = [[o[1], o[2]] for o in ops
                   if any(k in o[3] for k in keys)]
            by_scope[label] = total(clip(sel, w0, w1))
        by_module = {}
        for label, names in (modules or {}).items():
            runs = union([[a, b] for n, a, b in ex["modules"].get(dev, [])
                          if n in names])
            sel = [[o[1], o[2]] for o in ops
                   if _inside((o[1] + o[2]) / 2, runs)]
            by_module[label] = total(clip(sel, w0, w1))
        coll = clip([[o[1], o[2]] for o in ops
                     if COLLECTIVE.match(o[4])], w0, w1)
        comp = clip([[o[1], o[2]] for o in ops
                     if not COLLECTIVE.match(o[4])], w0, w1)
        per_dev[dev] = {"busy_ns": total(busy), "scope_ns": by_scope,
                        "module_ns": by_module,
                        "collective_ns": total(coll),
                        "collective_exposed_ns": total(minus(coll, comp))}
        for o in ops:
            label = f"{o[4]} {'/'.join(o[3].split('/')[-2:])}".strip()
            op_time[label] = op_time.get(label, 0) + (min(o[2], w1)
                                                       - max(o[1], w0))
        for a, b in gaps(busy, w0, w1):
            all_gaps.append((b - a, a, b, dev))
    all_gaps.sort(reverse=True)
    idle = []
    for dur, a, b, dev in all_gaps[:top]:
        idle.append([_host_label(host, a, b), dur / 1e9])
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    return {"window_ns": w1 - w0, "scopes": dict(scopes),
            "modules": dict(modules or {}), "devices": per_dev,
            "device_ops": [[n, t / 1e9] for n, t in ops_top],
            "idle_gaps": idle}


def _inside(t, runs):
    """Whether ``t`` lies in one of the sorted disjoint ``runs``."""
    i = bisect.bisect_right(runs, [t, float("inf")]) - 1
    return i >= 0 and runs[i][0] <= t <= runs[i][1]


def _host_label(host, a, b):
    """The innermost host span that covers most of [a, b]."""
    best, best_cov, best_len = "no host span", 0, None
    for name, h0, h1 in host:
        cov = min(h1, b) - max(h0, a)
        if cov <= 0:
            continue
        ln = h1 - h0
        if cov > best_cov or (cov == best_cov and ln < best_len):
            best, best_cov, best_len = name, cov, ln
    return best
