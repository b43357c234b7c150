"""train.plugin_rounds (rounds): a reader that a family brings as a file
of its own. It declares a trace scope label of its own, which the
harness reduces the trace by, and reads a counter of the program: the
rounds whose gradient norm the window's segments reported
(``ctx["program"]["grad_norm"]``). On a chip every device has its label's
device time; a CPU trace holds no device, so there it reads the counter
alone."""

SCOPES = {"plugin_fwd_bwd": ["dsgd.fwd_bwd"]}


def read(ctx):
    red = ctx["reduced"]
    if red["scopes"].get("plugin_fwd_bwd") != SCOPES["plugin_fwd_bwd"]:
        return None
    if any("plugin_fwd_bwd" not in d["scope_ns"]
           for d in red["devices"].values()):
        return None
    return float(len(ctx["program"]["grad_norm"]))
