"""train.update_roofline (%): the HBM bytes AdamW needs per local step
(read parameters, gradients and both moments, write parameters and
moments, in their stored dtypes, for every agent on the chip) over the
HBM peak, over the device time under ``dsgd.local_update`` per local
step, on the busiest chip. Moves train_tokens_per_s."""
from bench import counts

SCOPES = {"local_update": ["dsgd.local_update"]}


def read(ctx):
    cfg = ctx["cfg"]
    steps = ctx["counts"]["local_steps"]
    t = max(d["scope_ns"]["local_update"]
            for d in ctx["reduced"]["devices"].values())
    if not steps or not t:
        return None
    agents = cfg["job"]["agents"] / ctx["chips"]
    moment = {"bf16": "bfloat16", "f32": "float32"}[cfg["job"]["moments"]]
    nbytes = agents * counts.adamw_bytes(counts.params(cfg), moment=moment)
    least_s = nbytes / ctx["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (t / steps / 1e9)
