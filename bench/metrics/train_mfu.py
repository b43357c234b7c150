"""train_mfu (%): model FLOPs of the forward and backward passes of every
token trained in the traced window, over the window, over the chips'
bf16 peak (f32 operands at the default precision run as single-pass
bf16 on the MXU). Moves train_tokens_per_s."""
from bench import counts


def read(ctx):
    red, cfg = ctx["reduced"], ctx["cfg"]
    window_s = red["window_ns"] / 1e9
    tokens = ctx["counts"]["tokens"]
    if not tokens or window_s <= 0:
        return None
    flops = tokens * counts.train_flops_per_token(cfg, cfg["job"]["seq_len"])
    return 100.0 * flops / window_s / (
        ctx["chips"] * ctx["peaks"]["bf16_flops_per_s"])
