"""serve.engine_idle_share (%): the share of the traced window in which no
operation runs on the device while the host is inside one of the
engine's spans ``serve.step`` or ``serve.admit``, averaged over the
chips. Spans are chosen by exact name: the Python tracer's events of
``bench/serve.py`` hold ``serve`` too. What serve.idle_share holds beyond
it is the caller's: waiting for requests, submitting them, sleeping.
Reads the trace's extract (``ctx["extract"]``). Moves
serve_itl_p95_ms."""
from bench import trace

ENGINE_SPANS = ("serve.step", "serve.admit")


def _overlap(a, b):
    """ns that two sorted disjoint interval lists share (one sweep)."""
    out, j = 0, 0
    for a0, a1 in a:
        while j < len(b) and b[j][1] <= a0:
            j += 1
        k = j
        while k < len(b) and b[k][0] < a1:
            out += min(a1, b[k][1]) - max(a0, b[k][0])
            k += 1
    return out


def read(ctx):
    ex = ctx.get("extract")
    if not ex or not ex["devices"]:
        return None
    w0, w1 = trace.window(ex)
    spans = trace.union(trace.clip([[a, b] for name, a, b in ex["host"]
                                    if name in ENGINE_SPANS], w0, w1))
    if not spans or w1 <= w0:
        return None
    inside = trace.total(spans)
    idle = [inside - _overlap(spans, trace.union(trace.clip(
        [[o[1], o[2]] for o in ops], w0, w1)))
        for ops in ex["devices"].values()]
    return 100.0 * sum(idle) / len(idle) / (w1 - w0)
