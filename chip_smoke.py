"""Bring-up smoke test: the decentralized trainer and the merged-model
server, run on a TPU through the entry points a user calls.

    python chip_smoke.py              # one chip: phases (a), (b), (c)
    python chip_smoke.py --chips 4    # four-chip host: phase (d) only

(a) Train: ``repro.launch.train`` at ``--preset chip`` (olmo-1b at every
    published width, depth cut), m agents, final-merge schedule, saving
    the merged model. Every round's loss is finite, the loss falls, the
    consensus distance is 0 after the final merge (to the last bits of a
    row), evals are finite.
(b) Kernels: the main-path Pallas kernels at the preset's panel width D,
    compiled for the chip, against their ``kernels/ref.py`` oracles run
    by XLA on the same chip (tolerances in ``KERNEL_TOL``).
(c) Serve: ``repro.launch.serve`` restores the merged model and serves a
    few requests through 4 slots; each request is compared with a lone
    ``generate`` of it at temperature 0 (tokens; where a token differs,
    the engine's pick must be a near-tie of the lone run's logits). On a
    TPU the engine serves its bfloat16 operand copy of the dense weights
    (``operand_bytes``), ``generate`` the float32 params.
(d) ``--chips 4``: phase (a) with the agent rows laid over the host's
    chips (``--mesh host``), against the same seed replicated on one chip:
    per-round losses, the merged model and its eval within ``SHARD_TOL``.

One process owns the chip(s) for the whole run. It stops with a non-zero
exit code, and prints no result, unless JAX's first device is a TPU; any
failed check raises. Each phase prints one line; the last line of stdout
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
WORK = os.path.join(HERE, ".smoke")

# published widths, depth cut (repro.configs.preset_config)
PRESET = "chip"
# phase (a)/(d): m agents (a multiple of 4, so (d) lays one agent per chip).
# bf16 moments: the XLA path of moments=int8 holds about five f32 (m, D)
# temporaries in the update and does not fit one chip at m=4
AGENTS = 4
TRAIN_ARGS = ["--arch", "olmo-1b", "--agents", str(AGENTS),
              "--rounds", "4", "--segment", "2", "--local-steps", "2",
              "--batch", "4", "--seq", "512",
              "--schedule", "final_merge", "--residency", "moments=bf16",
              "--lr", "1e-3", "--seed", "0"]
# phase (b): max |kernel - oracle| / max |oracle| allowed per output; for
# the int8 outputs, max |kernel - oracle| in quantization steps (one step
# where a division lands an ulp apart). Matmuls over the agent axis run
# at highest precision in the kernel and in its oracle.
KERNEL_TOL = {"gossip_mix": 1e-5, "panel_reduce.mean": 1e-5,
              "panel_reduce.sq": 1e-3, "int8g.quantize": 1.0,
              "int8g.dequantize": 1e-6, "adamw_fused.p": 1e-5,
              "adamw_fused.q": 1.0, "adamw_fused.scale": 1e-5}
# phase (c): a differing token must be within this many logits of the
# lone run's best token at that position
LOGIT_TOL = 1e-2
# after the final merge the agent rows agree to the last bits: Xi over the
# merged model's norm
CONSENSUS_TOL = 1e-6
# phase (d): sharded against replicated, relative. Adam scales every
# coordinate's step to about lr whatever its gradient, so last-bit
# differences between the layouts' matmuls flip steps on near-zero
# gradients: the merged weights drift apart (1.8e-3 relative L2 measured
# on a v5e 2x2) while losses and the merged eval agree to ~1e-5
SHARD_TOL = {"loss": 1e-4, "merged": 1e-2, "merged_eval": 1e-4}


def log(phase, **kw):
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in kw.items()),
          flush=True)


def check(ok, what):
    if not ok:
        raise AssertionError(what)


def peak_bytes(jax):
    return max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in jax.local_devices())


def run_train(jax, mesh, tag):
    from repro.launch import train
    t0 = time.perf_counter()
    res = train.main(TRAIN_ARGS + [
        "--preset", PRESET, "--mesh", mesh, "--out", os.path.join(WORK, tag),
        "--save-merged", os.path.join(WORK, tag, "merged.ckpt")])
    dt = time.perf_counter() - t0
    hist = res["history"]
    losses = [h["train_loss"] for h in hist]
    last = hist[-1]
    check(all(math.isfinite(v) for v in losses), f"non-finite loss {losses}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    import jax.numpy as jnp
    norm = math.sqrt(sum(float(jnp.sum(jnp.square(x)))
                         for x in jax.tree.leaves(res["merged"])))
    check(last["consensus"] <= CONSENSUS_TOL * norm,
          f"consensus {last['consensus']} after the final merge "
          f"(merged model norm {norm})")
    check(math.isfinite(last["merged_eval"])
          and math.isfinite(last["local_eval"]),
          f"eval merged={last['merged_eval']} local={last['local_eval']}")
    log(tag, seconds=f"{dt:.1f}", losses=losses,
        consensus=last["consensus"], merged_eval=last["merged_eval"],
        local_eval=last["local_eval"], peak_bytes=peak_bytes(jax))
    return res


def _rel(a, b):
    """max |a - b| / max |b|, reduced on the device (the panels are GBs)."""
    import jax.numpy as jnp
    a, b = jnp.asarray(a, jnp.float32), jnp.asarray(b, jnp.float32)
    return float(jnp.max(jnp.abs(a - b))
                 / jnp.maximum(jnp.max(jnp.abs(b)), 1e-30))


def _steps(a, b):
    """max |a - b| of two int8 panels, in quantization steps."""
    import jax.numpy as jnp
    return float(jnp.max(jnp.abs(a.astype(jnp.int32) - b.astype(jnp.int32))))


def run_kernels(jax, D):
    """Each main-path kernel at panel width D against its XLA oracle."""
    import jax.numpy as jnp
    from repro.kernels import ref
    from repro.kernels.gossip_mix import gossip_mix_panel
    from repro.kernels.opt_fused import adamw_fused_int8_panel
    from repro.kernels.panel_reduce import panel_mean_consensus
    from repro.kernels.wire_quant import (dequantize_int8_grouped_panel,
                                          quantize_int8_grouped_panel)
    from repro.optim import make_optimizer
    from repro.residency import get_storage

    t0 = time.perf_counter()
    diffs = {}
    key = jax.random.PRNGKey(0)
    m = AGENTS
    x = jax.random.normal(key, (m, D), jnp.float32)
    W = jnp.full((m, m), 1.0 / m, jnp.float32)
    diffs["gossip_mix"] = _rel(gossip_mix_panel(W, x),
                               ref.gossip_mix_ref(W, x))
    mean_k, sq_k = panel_mean_consensus(x)
    mean_r, sq_r = ref.panel_mean_consensus_ref(x)
    diffs["panel_reduce.mean"] = _rel(mean_k, mean_r)
    diffs["panel_reduce.sq"] = _rel(sq_k, sq_r)
    del mean_k, mean_r, x
    gc.collect()

    # the codec kernels on 2 agent rows: the oracle's f32 temporaries and
    # the kernels' grouped views of 4 rows would not fit beside each other
    st = get_storage("int8")  # the moments=int8 residency storage
    x = jax.random.normal(jax.random.fold_in(key, 3), (2, D), jnp.float32)
    u = jax.random.uniform(jax.random.fold_in(key, 1), (2, D))
    scale = ref.int8_group_scale_ref(x, st.group)
    q_k, _ = quantize_int8_grouped_panel(x, scale, u, group=st.group)
    q_r = ref.quantize_int8_grouped_ref(x, scale, u, group=st.group)
    diffs["int8g.quantize"] = _steps(q_k, q_r)
    del q_k, u
    diffs["int8g.dequantize"] = _rel(
        dequantize_int8_grouped_panel(q_r, scale, group=st.group),
        ref.dequantize_int8_grouped_ref(q_r, scale, group=st.group))
    del q_r, scale, x
    gc.collect()

    # fused AdamW on one agent row, for the same reason
    opt = make_optimizer("adamw", 1e-3)
    ks = jax.random.split(jax.random.fold_in(key, 2), 6)
    g, p = (jax.random.normal(k, (1, D)) for k in ks[:2])
    zm = st.transform_fwd(0.1 * jax.random.normal(ks[2], (1, D)))
    zv = st.transform_fwd(jnp.abs(0.01 * jax.random.normal(ks[3], (1, D))))
    sm = ref.int8_group_scale_ref(zm, st.group)
    sv = ref.int8_group_scale_ref(zv, st.group)
    qm = ref.quantize_int8_grouped_ref(zm, sm, group=st.group)
    qv = ref.quantize_int8_grouped_ref(zv, sv, group=st.group)
    del zm, zv
    um, uv = (jax.random.uniform(k, (1, D)) for k in ks[4:])
    lr, bc1, bc2 = opt.hyper(jnp.ones((1,), jnp.int32))
    args = (g, p, qm, sm, qv, sv, um, uv)
    cols = [jnp.ones((1, 1)) * jnp.reshape(h, (-1, 1))
            for h in (lr, bc1, bc2)]
    kw = dict(group=st.group, core=opt.core, transform_fwd=st.transform_fwd,
              transform_inv=st.transform_inv)
    out_k = adamw_fused_int8_panel(*args, lr, bc1, bc2, **kw)
    out_r = ref.adamw_fused_int8_ref(*args, *cols, **kw)
    diffs["adamw_fused.p"] = _rel(out_k[0], out_r[0])
    diffs["adamw_fused.q"] = max(_steps(out_k[1], out_r[1]),
                                 _steps(out_k[3], out_r[3]))
    diffs["adamw_fused.scale"] = max(_rel(out_k[2], out_r[2]),
                                     _rel(out_k[4], out_r[4]))
    del out_k, out_r, args, g, p, qm, qv, um, uv
    gc.collect()
    dt = time.perf_counter() - t0
    log("b.kernels", seconds=f"{dt:.1f}", D=D,
        max_diff=json.dumps(diffs), peak_bytes=peak_bytes(jax))
    for name, d in diffs.items():
        check(d <= KERNEL_TOL[name],
              f"{name}: difference {d} beyond {KERNEL_TOL[name]}")


def run_serve(jax, merged_path):
    import numpy as np
    import jax.numpy as jnp
    from repro.launch import serve
    from repro.serving import generate
    from repro.serving.engine import make_prefill_fn

    t0 = time.perf_counter()
    res = serve.main(["--arch", "olmo-1b", "--preset", PRESET,
                      "--restore", merged_path, "--concurrency", "4",
                      "--requests", "6", "--prompt-len", "64",
                      "--max-new", "16", "--seed", "0"])
    t_serve = time.perf_counter() - t0
    model, params, max_len = res["model"], res["params"], res["max_len"]
    exact, near_ties, gaps = 0, 0, []
    for r in res["requests"]:
        got = np.asarray(res["outputs"][r.rid])
        ref_toks = np.asarray(generate(
            model, params, {"tokens": jnp.asarray(r.tokens[None])},
            r.max_new, max_len=max_len)[0])
        check(got.shape == ref_toks.shape,
              f"request {r.rid}: {got.shape} tokens, lone {ref_toks.shape}")
        bad = np.nonzero(got != ref_toks)[0]
        if not len(bad):
            exact += 1
            continue
        # first differing position: the lone run's logits there, given the
        # common prefix, must rank the engine's token within LOGIT_TOL
        i = int(bad[0])
        prompt = np.concatenate([r.tokens, ref_toks[:i]])[None]
        logits, _ = make_prefill_fn(model, max_len=max_len)(
            params, {"tokens": jnp.asarray(prompt, jnp.int32)})
        row = np.asarray(logits[0, -1, :model.cfg.vocab_size], np.float64)
        gap = float(row[ref_toks[i]] - row[got[i]])
        gaps.append(gap)
        check(gap <= LOGIT_TOL,
              f"request {r.rid} diverges at token {i}: logit gap {gap}")
        near_ties += 1
    dt = time.perf_counter() - t0
    log("c.serve", seconds=f"{dt:.1f}", serve_seconds=f"{t_serve:.1f}",
        requests=len(res["requests"]), bit_exact=exact,
        near_ties=near_ties, logit_gaps=gaps,
        operand_bytes=res["operand_bytes"], peak_bytes=peak_bytes(jax))


def run_sharded(jax):
    """Phase (d): the host mesh against one replicated chip."""
    import numpy as np
    sharded = run_train(jax, "host", "d.sharded")
    merged_s = jax.tree.map(np.asarray, sharded.pop("merged"))
    gc.collect()
    single = run_train(jax, "none", "d.replicated")
    merged_1 = jax.tree.map(np.asarray, single["merged"])
    loss_d = max(abs(a["train_loss"] - b["train_loss"])
                 / abs(b["train_loss"])
                 for a, b in zip(sharded["history"], single["history"]))
    num = sum(float(np.sum((a - b) ** 2)) for a, b in zip(
        jax.tree.leaves(merged_s), jax.tree.leaves(merged_1)))
    den = sum(float(np.sum(b ** 2)) for b in jax.tree.leaves(merged_1))
    merged_d = math.sqrt(num / den)
    a, b = (r["history"][-1]["merged_eval"] for r in (sharded, single))
    eval_d = abs(a - b) / abs(b)
    log("d.compare", loss_rel_diff=loss_d, merged_rel_l2=merged_d,
        merged_eval_rel_diff=eval_d)
    check(loss_d <= SHARD_TOL["loss"], f"per-round loss differs by {loss_d}")
    check(merged_d <= SHARD_TOL["merged"],
          f"merged model differs by {merged_d} (relative L2)")
    check(eval_d <= SHARD_TOL["merged_eval"],
          f"merged eval differs by {eval_d}")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4])
    args = ap.parse_args()

    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"chip_smoke: JAX found no TPU (first device: "
                 f"{dev.platform}); nothing was run")
    count = len(jax.devices())
    if count < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but {count} devices")
    sys.path.insert(0, os.path.join(HERE, "src"))
    from repro.launch.compile_cache import use_compile_cache
    cache = use_compile_cache()
    log("start", platform=dev.platform, kind=dev.device_kind, count=count,
        compile_cache=cache)
    os.makedirs(WORK, exist_ok=True)

    if args.chips == 4:
        run_sharded(jax)
    else:
        res = run_train(jax, "none", "a.train")
        D = sum(x.size for x in jax.tree.leaves(res.pop("merged")))
        gc.collect()
        run_kernels(jax, D)
        run_serve(jax, os.path.join(WORK, "a.train", "merged.ckpt"))
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": count}}), flush=True)


if __name__ == "__main__":
    main()
