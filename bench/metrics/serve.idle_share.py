"""serve.idle_share (%): the share of the traced window in which no
operation runs on the device: the engine's host scheduler (admission,
the host-side first-token sample, the (C,) token fetch per step) and the
wait for requests. Its longest gaps, by host span, are the line's
``breakdown``. Moves serve_itl_p95_ms."""


def read(ctx):
    red = ctx["reduced"]
    busy = [d["busy_ns"] for d in red["devices"].values()]
    if not busy or red["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / red["window_ns"])
