"""train.relayout_ms (ms): device time under ``dsgd.local_grad`` and not
under ``dsgd.fwd_bwd`` per local step, on the busiest chip: moving each
agent's row between the (m, D) panel and the model's leaves, and
stacking the gradient rows. With ``train.fwd_bwd_ms`` it adds up to
``train.local_grad_ms``; a program without the ``dsgd.fwd_bwd`` scope
reads nothing. Moves train_tokens_per_s."""

SCOPES = {"local_grad": ["dsgd.local_grad"], "fwd_bwd": ["dsgd.fwd_bwd"]}


def read(ctx):
    steps = ctx["counts"]["local_steps"]
    devs = ctx["reduced"]["devices"].values()
    if not steps or not any(d["scope_ns"].get("fwd_bwd") for d in devs):
        return None
    t = max(d["scope_ns"]["local_grad"] - d["scope_ns"]["fwd_bwd"]
            for d in devs)
    return t / steps / 1e6
