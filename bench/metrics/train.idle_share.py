"""train.idle_share (%): the share of the traced window in which no
operation runs on the device, averaged over the chips. Its longest gaps,
by the host span they fall in, are the line's ``breakdown``. Moves
train_tokens_per_s."""


def read(ctx):
    red = ctx["reduced"]
    busy = [d["busy_ns"] for d in red["devices"].values()]
    if not busy or red["window_ns"] <= 0:
        return None
    return 100.0 * (1.0 - sum(busy) / len(busy) / red["window_ns"])
