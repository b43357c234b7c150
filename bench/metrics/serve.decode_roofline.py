"""serve.decode_roofline (%): the HBM bytes the window's decode steps need
(every weight once per step, and the keys and values of the live
positions of the live slots only, so reading the empty rest of the dense
cache counts as waste) over the HBM peak, over the device time of the
decode-step programs (decode and sample). Moves serve_itl_p95_ms."""
from bench import counts

MODULES = {"decode": ["jit_step_fn"]}


def read(ctx):
    cfg = ctx["cfg"]
    t = max(d["module_ns"]["decode"]
            for d in ctx["reduced"]["devices"].values())
    steps = ctx["counts"]["decode_contexts"]
    if not t or not steps:
        return None
    nbytes = sum(counts.decode_step_bytes(cfg, sum(step)) for step in steps)
    return 100.0 * nbytes / ctx["peaks"]["hbm_bytes_per_s"] / (t / 1e9)
