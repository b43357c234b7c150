"""serve.prefill_mfu (%): the FLOPs of the window's prefills over the device
time of the prefill programs, over the bf16 peak. Moves
serve_itl_p95_ms: a prefill runs between two decode steps, so it
lengthens the gap between two tokens of every live request."""
from bench import counts

MODULES = {"prefill": ["jit_prefill"]}


def read(ctx):
    cfg = ctx["cfg"]
    t = max(d["module_ns"]["prefill"]
            for d in ctx["reduced"]["devices"].values())
    flops = sum(counts.prefill_flops(cfg, n)
                for n in ctx["counts"]["prefill_lengths"])
    if not t or not flops:
        return None
    return 100.0 * flops / (t / 1e9) / ctx["peaks"]["bf16_flops_per_s"]
