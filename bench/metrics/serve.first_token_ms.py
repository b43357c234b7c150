"""serve.first_token_ms (ms): the median, over the requests the engine
admitted in the window, of the time from the start of a request's
admission to its first token on the host (prefill, insert, the
host-side sample and its fetch), read from the engine's per-request
records (``ctx["engine"]``, as for serve.queue_wait_ms). Moves
serve_itl_p95_ms: an admission runs between two decode steps, so it
lengthens the gap between two tokens of every live request."""
import statistics


def read(ctx):
    recs = (ctx.get("engine") or {}).get("requests")
    if not recs:
        return None
    return 1e3 * statistics.median(r["first"] - r["admit"] for r in recs)
