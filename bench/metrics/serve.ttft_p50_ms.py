"""serve.ttft_p50_ms (ms): the median, over every request due in the
window, of the time from its due time to its first token, on the host's
clock (``ctx["ttft"]``, seconds, as ``serve.latency_metrics`` gives it: a
request with no first token by the window's end counts at its elapsed
time). Admission runs only between decode steps, so each request waits
out part of the step in flight; where that part falls moves the median
of a window's ~100 requests by several milliseconds from run to run.
Moves serve_itl_p95_ms, the length of the step waited out."""
from bench import common


def read(ctx):
    ttft = ctx.get("ttft")
    if not ttft:
        return None
    return 1e3 * common.quantile(ttft, 0.5)
