"""train.local_grad_ms (ms): device time under the ``dsgd.local_grad``
scope (every agent's forward and backward) per local step, on the
busiest chip. Moves train_tokens_per_s."""

SCOPES = {"local_grad": ["dsgd.local_grad"]}


def read(ctx):
    steps = ctx["counts"]["local_steps"]
    t = max(d["scope_ns"]["local_grad"]
            for d in ctx["reduced"]["devices"].values())
    if not steps or not t:
        return None
    return t / steps / 1e6
