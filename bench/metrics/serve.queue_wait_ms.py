"""serve.queue_wait_ms (ms): the median, over the requests the engine
admitted in the window, of the time from a request's arrival to the start
of its admission, read from the engine's per-request records
(``ctx["engine"]``: ``ServingEngine.snapshot()`` taken right after the
window). It holds the wait behind the step in flight and for a free
slot. Moves serve_itl_p95_ms, the length of the step waited out."""
import statistics


def read(ctx):
    recs = (ctx.get("engine") or {}).get("requests")
    if not recs:
        return None
    return 1e3 * statistics.median(r["admit"] - r["arrival"] for r in recs)
