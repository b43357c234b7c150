"""Serve a (merged) model through the continuous-batching engine.

CPU demo — heterogeneous-length requests streaming through slotted decode:

    PYTHONPATH=src python -m repro.launch.serve --arch olmo-1b --preset cpu \
        --concurrency 4 --requests 8 --max-new 16 [--stream]

optionally restoring the artifact produced by ``launch.train
--save-merged`` via ``--restore`` (same ``--preset`` as the training run:
``chip`` keeps every published width and cuts depth, as in training).
``--one-shot`` runs the plain static batched :func:`repro.serving.generate`
path instead.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro import telemetry
from repro.checkpoint import restore
from repro.configs import PRESETS, get_config, preset_config
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.serving import Request, ServingEngine, generate


def _request_inputs(cfg, i, S, k_prompt, k_mm, k_frames):
    """Prompt + multimodal extras for demo request ``i`` (independent PRNG
    streams, folded per request)."""
    toks = jax.random.randint(jax.random.fold_in(k_prompt, i), (S,), 0,
                              cfg.vocab_size)
    extras = {}
    if cfg.mm_prefix > 0:
        extras["patch_embeds"] = jax.random.normal(
            jax.random.fold_in(k_mm, i), (cfg.mm_prefix, cfg.d_model))
    if cfg.encoder_layers:
        extras["frame_embeds"] = jax.random.normal(
            jax.random.fold_in(k_frames, i), (S, cfg.d_model))
    return np.asarray(toks, np.int32), extras


def main(argv=None):
    """Serve the requests ``argv`` (default: the command line) describes.
    Returns {"outputs": {rid: tokens}, "requests", "model", "params",
    "max_len"} for callers that drive the server in-process (None for
    ``--one-shot``)."""
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b")
    ap.add_argument("--preset", default="cpu", choices=PRESETS)
    ap.add_argument("--concurrency", type=int, default=4,
                    help="decode slots held live at once")
    ap.add_argument("--requests", type=int, default=8,
                    help="demo requests fed through the engine")
    ap.add_argument("--prompt-len", type=int, default=32,
                    help="longest demo prompt (half of them use len//2)")
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=0,
                    help="slot length; 0 = prompt+mm_prefix+max_new")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--eos-id", type=int, default=-1,
                    help="stop token (>=0 enables early slot retirement)")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as slots emit them")
    ap.add_argument("--one-shot", action="store_true",
                    help="legacy path: one static generate() batch")
    ap.add_argument("--restore", default="")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--events", default="",
                    help="typed request-lifecycle JSONL event stream "
                         "(submit/admit/retire + serve_start/serve_end), "
                         "schema-validated at emit time")
    ap.add_argument("--profile", default="",
                    help="capture a jax profiler trace of the serving "
                         "loop into this logdir")
    args = ap.parse_args(argv)

    cfg = preset_config(get_config(args.arch), args.preset)
    model = build_model(cfg)
    # independent PRNG streams: params / prompts / patch embeds / frame
    # embeds / sampling (the seed path used to reuse ONE key for all five)
    k_params, k_prompt, k_mm, k_frames, k_sample = jax.random.split(
        jax.random.PRNGKey(args.seed), 5)
    params = model.init_params(k_params)
    if args.restore:
        params = restore(args.restore, params)
        print("restored", args.restore)
    eos_id = args.eos_id if args.eos_id >= 0 else None

    if args.one_shot:
        B, S = args.requests, args.prompt_len
        batch = {"tokens": jnp.stack([jnp.asarray(_request_inputs(
            cfg, i, S, k_prompt, k_mm, k_frames)[0]) for i in range(B)])}
        if cfg.mm_prefix > 0:
            batch["patch_embeds"] = jax.random.normal(
                k_mm, (B, cfg.mm_prefix, cfg.d_model))
        if cfg.encoder_layers:
            batch["frame_embeds"] = jax.random.normal(
                k_frames, (B, S, cfg.d_model))
        t0 = time.time()
        out = generate(model, params, batch, args.max_new,
                       temperature=args.temperature, rng=k_sample,
                       eos_id=eos_id)
        dt = time.time() - t0
        print(f"generated {out.shape} in {dt:.2f}s "
              f"({B * args.max_new / dt:.1f} tok/s)")
        print(out[:2])
        return None

    # two prompt-length buckets -> exactly two prefill compiles
    lengths = [args.prompt_len, max(1, args.prompt_len // 2)]
    max_len = args.max_len or (args.prompt_len + max(0, cfg.mm_prefix)
                               + args.max_new)
    serve_cfg = {k: vars(args)[k] for k in (
        "arch", "preset", "concurrency", "requests", "prompt_len",
        "max_new", "temperature", "eos_id", "seed")}
    log = telemetry.EventLog(args.events or None,
                             run_id=telemetry.make_run_id(serve_cfg))
    log.emit("serve_start", run_id=log.run_id,
             schema=telemetry.SCHEMA_VERSION, config=serve_cfg)
    engine = ServingEngine(model, params, max_concurrency=args.concurrency,
                           max_len=max_len, eos_id=eos_id,
                           temperature=args.temperature, rng=k_sample,
                           events=log)
    reqs = []
    for i in range(args.requests):
        toks, extras = _request_inputs(cfg, i, lengths[i % len(lengths)],
                                       k_prompt, k_mm, k_frames)
        reqs.append(Request(rid=i, tokens=toks, max_new=args.max_new,
                            extras=extras))
    stream_cb = ((lambda rid, t: print(f"  req {rid}: {t}"))
                 if args.stream else None)
    prof = telemetry.profile_trace(args.profile,
                                   enabled=bool(args.profile)).start()
    t0 = time.time()
    out = engine.serve(reqs, stream=stream_cb)
    dt = time.time() - t0
    prof.stop()
    n_tok = sum(len(v) for v in out.values())
    snap = engine.snapshot()
    print(telemetry.format_event(log.emit(
        "serve_end", requests=len(out), tokens=n_tok,
        ticks=snap["ticks"], occupancy=snap["occupancy"])), flush=True)
    lat = snap["latency"]
    recs = snap["requests"]
    print(f"  {n_tok / dt:.1f} tok/s | "
          f"ttft p50/p99 {lat['ttft_s']['p50_s'] * 1e3:.1f}/"
          f"{lat['ttft_s']['p99_s'] * 1e3:.1f} ms | queue p50 "
          f"{lat['queue_wait_s']['p50_s'] * 1e3:.1f} ms | decode step "
          f"p50 {lat['decode_step_s']['p50_s'] * 1e3:.1f} ms | per-token "
          f"p50 {lat['per_token_s']['p50_s'] * 1e3:.1f} ms")
    # exact medians over the engine's per-request records (the histograms
    # above interpolate inside 33% buckets)
    print(f"  per request (median of {len(recs)}): queue wait "
          f"{np.median([r['admit'] - r['arrival'] for r in recs]) * 1e3:.1f}"
          f" ms | admission to first token "
          f"{np.median([r['first'] - r['admit'] for r in recs]) * 1e3:.1f}"
          f" ms")
    log.emit_op("serve_latency", **{k: lat[k] for k in lat})
    log.close()
    for rid in sorted(out)[:2]:
        print(f"req {rid}:", out[rid])
    if args.events:
        print(f"events: {args.events}")
    return {"outputs": out, "requests": reqs, "model": model,
            "params": params, "max_len": max_len,
            "operand_bytes": snap["operand_bytes"]}


if __name__ == "__main__":
    main()
