"""serve_mfu (%): the FLOPs of every prefill and of every live slot's
decoded token in the traced window, over the window, over the bf16 peak.
Moves serve_tokens_per_s."""
from bench import counts


def read(ctx):
    cfg, c = ctx["cfg"], ctx["counts"]
    window_s = ctx["reduced"]["window_ns"] / 1e9
    flops = (sum(counts.prefill_flops(cfg, n) for n in c["prefill_lengths"])
             + sum(counts.decode_flops(cfg, x)
                   for step in c["decode_contexts"] for x in step))
    if not flops or window_s <= 0:
        return None
    return 100.0 * flops / window_s / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
