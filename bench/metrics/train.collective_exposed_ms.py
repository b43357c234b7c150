"""train.collective_exposed_ms (ms): device time of cross-chip collectives
(all-reduce, all-gather, reduce-scatter, collective-permute, all-to-all,
their async start and done ops included) that no other op on the same
chip overlaps, per round, on the busiest chip. Reads the trace
reduction's ``collective_exposed_ns``; a window with no collective reads
nothing. Moves train_tokens_per_s."""


def read(ctx):
    rounds = ctx["counts"]["rounds"]
    devs = ctx["reduced"]["devices"].values()
    if not rounds or not any(d["collective_ns"] for d in devs):
        return None
    t = max(d["collective_exposed_ns"] for d in devs)
    return t / rounds / 1e6
