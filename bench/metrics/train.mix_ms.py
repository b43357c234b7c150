"""train.mix_ms (ms): device time under the ``panel.*`` scopes (gossip mix,
folded mean, global merge, consensus) and ``merge.panel``, per round, on
the busiest chip. Moves train_tokens_per_s."""

SCOPES = {"mix": ["panel.", "merge.panel"]}


def read(ctx):
    rounds = ctx["counts"]["rounds"]
    t = max(d["scope_ns"]["mix"] for d in ctx["reduced"]["devices"].values())
    if not rounds or not t:
        return None
    return t / rounds / 1e6
