"""train.fwd_bwd_ms (ms): device time under the ``dsgd.fwd_bwd`` scope (the
model's forward and backward inside each agent's local step, without the
relayout around it) per local step, on the busiest chip. Reads the
trace reduction's scope label ``fwd_bwd``; a program without the scope
reads nothing. Moves train_tokens_per_s."""

SCOPES = {"fwd_bwd": ["dsgd.fwd_bwd"]}


def read(ctx):
    steps = ctx["counts"]["local_steps"]
    t = max(d["scope_ns"].get("fwd_bwd", 0)
            for d in ctx["reduced"]["devices"].values())
    if not steps or not t:
        return None
    return t / steps / 1e6
