"""Serving cells: independent users against the merged model, an open loop
that drives ``repro.serving.ServingEngine``'s ``submit``, ``admit`` and
``step`` itself.

Set-up makes the weights on the device from the seed, builds the engine,
and warms every prompt length of the mix and the decode step through the
same engine. In the window each request is submitted when it falls due,
whatever the engine is doing; every latency runs from the request's due
time. A first token counts when ``admit()`` returns it to the caller; a
decoded token when ``step()`` does.

After the window: a sample drawn from the seed of the requests the
engine finished, with the longest among them, goes through the plain
reference, which compares each served token with its own best logit;
and the engine's own compiled prefill answers each sampled prompt once
more, its logits compared with the reference's at that position.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import time

import numpy as np

from bench import common, gen, program
from bench import reference as ref

class Server:
    """The engine, its weights and the cell's requests for one seed."""

    def __init__(self, jax, cfg, traffic, seed, seconds, *, model_wrap=None):
        from repro.models import build_model
        from repro.serving import ServingEngine
        self.jax, self.cfg, self.traffic = jax, cfg, traffic
        self.model = build_model(program.model_config(cfg))
        if model_wrap is not None:
            self.model = model_wrap(self.model)
        self.k_w = common.seed_key(jax, seed, 1)
        self.make_params = jax.jit(lambda k: ref.make_params(cfg, k))
        params = self.make_params(self.k_w)
        program.check_layout(jax, cfg, self.model, params)
        self.params = params
        sv = cfg["serving"]
        self.engine = ServingEngine(
            self.model, program.to_program(cfg, params),
            max_concurrency=sv["slots"], max_len=sv["max_len"],
            temperature=0.0, rng=common.seed_key(jax, seed, 2))
        (self.due, self.plen, self.nout), rng = gen.open_loop(
            traffic, seed, seconds)
        self.prompts = [rng.integers(0, cfg["vocab_size"], n, dtype=np.int32)
                        for n in self.plen]

    def warm(self):
        """Every prompt length of the mix through prefill, insert and the
        first-token sample, and the decode step, on this engine."""
        from repro.serving import Request
        e = self.engine
        for i, n in enumerate(self.traffic["prompt_lengths"]):
            e.submit(Request(rid=("warm", i), tokens=np.zeros(n, np.int32),
                             max_new=2))
        e.serve()
        e.reset()

    def window(self, seconds, on_tick=None):
        """The open loop for ``seconds``. Returns per request (submitted in
        the window): due, first-token and token times (seconds from the
        window's start), how late each submission ran, and the longest
        ``admit()`` and ``step()`` calls."""
        from repro.serving import Request
        e = self.engine
        N = len(self.due)
        first, times, late = {}, {}, []
        longest = {"admit": 0.0, "step": 0.0}
        i = 0
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            if now >= seconds:
                break
            while i < N and self.due[i] <= now:
                e.submit(Request(rid=i, tokens=self.prompts[i],
                                 max_new=int(self.nout[i]),
                                 arrival=t0 + self.due[i]))
                late.append(now - self.due[i])
                i += 1
            if e.queue and e.free_slots():
                waiting = [r.rid for r in e.queue]
                ta = time.perf_counter() - t0
                n = e.admit()
                t = time.perf_counter() - t0
                longest["admit"] = max(longest["admit"], t - ta)
                for rid in waiting[:n]:
                    first[rid] = t
                    times[rid] = [t]
            if e.live_slots():
                ts = time.perf_counter() - t0
                emitted = e.step()
                t = time.perf_counter() - t0
                longest["step"] = max(longest["step"], t - ts)
                for rid, _ in emitted:
                    times[rid].append(t)
            elif i < N:
                time.sleep(max(0.0, min(self.due[i], seconds)
                               - (time.perf_counter() - t0)))
            else:
                time.sleep(max(0.0, seconds - (time.perf_counter() - t0)))
            if on_tick is not None:
                on_tick(time.perf_counter() - t0, len(e.queue),
                        len(e.live_slots()))
        return {"submitted": i, "first": first, "times": times,
                "late": late, "longest": longest,
                "window": time.perf_counter() - t0}

    def drain(self):
        """Finish every request submitted so far (after a window, untimed)."""
        e = self.engine
        e.results = e.serve()

    def prefill_rows(self, rids):
        """(B, V) logits that the engine's own compiled prefill, the program
        that answered these requests in the window, gives for each one's
        prompt."""
        e, jnp = self.engine, self.jax.numpy
        V = self.cfg["vocab_size"]
        rows = []
        for rid in rids:
            lg, _ = e._prefill(e.params,
                               {"tokens": jnp.asarray(self.prompts[rid][None])})
            rows.append(np.asarray(lg[0, :V], np.float32))
        return np.stack(rows)

    def finish(self, seed):
        """After the window: (finished results, the sample of them that is
        checked, the engine's prefill logits of their prompts); then the
        engine is freed."""
        e = self.engine
        results = dict(e.results)
        rids = sample_finished(results, seed, self.traffic["check_sample"])
        rows = self.prefill_rows(rids) if rids else None
        e.caches = e._empty_row = None
        self.engine = None
        gc.collect()
        return results, rids, rows


def latency_metrics(w, due, seconds):
    """TTFT of every request due in the window (one with no first token by
    the window's end counts at its elapsed time), every gap between
    successive tokens, and the tokens delivered in the window."""
    ttft, itl, tokens = [], [], 0
    for rid in range(w["submitted"]):
        d = due[rid]
        f = w["first"].get(rid)
        ttft.append((f if f is not None and f <= seconds else seconds) - d)
        ts = [t for t in w["times"].get(rid, []) if t <= seconds]
        tokens += len(ts)
        itl.extend(np.diff(ts))
    return ttft, itl, tokens


def sample_finished(results, seed, k):
    """``k`` finished requests drawn from the seed, and the longest."""
    rids = sorted(r for r in results if not isinstance(r, tuple))
    if not rids:
        return []
    rng = np.random.default_rng([int(seed), 7])
    pick = set(rng.choice(rids, size=min(k, len(rids)), replace=False)
               .tolist())
    pick.add(max(rids, key=lambda r: (len(results[r]), r)))
    return sorted(pick)


def check_rows(server, results, rids):
    """(seq (B, T), first (B,), n (B,)) for the sampled requests: the
    prompt and the served tokens, padded to the cache length."""
    T = server.cfg["serving"]["max_len"]
    seq = np.zeros((len(rids), T), np.int32)
    first = np.zeros(len(rids), np.int32)
    n = np.zeros(len(rids), np.int32)
    for j, rid in enumerate(rids):
        p, out = server.prompts[rid], np.asarray(results[rid])
        seq[j, :len(p)] = p
        seq[j, len(p):len(p) + len(out)] = out
        first[j], n[j] = len(p) - 1, len(out)
    return seq, first, n


def reference_gaps(jax, cfg, params, seq, first, n, cand=None):
    """(per row the widest gap below the float32 reference's best logit of
    the served token, or of ``cand``, over the served positions; the
    reference's (B, V) logits at each row's last prompt token)."""
    jnp = jax.numpy
    with jax.default_matmul_precision("highest"):
        fn = jax.jit(ref.gaps(cfg))
        seq_d = jnp.asarray(seq)
        cand_d = ref.next_tokens(seq_d) if cand is None else jnp.asarray(cand)
        out, rows = [], []
        for j in range(seq.shape[0]):
            g, row = fn(params, seq_d[j:j + 1], cand_d[j:j + 1],
                        jnp.asarray(first[j:j + 1]), jnp.asarray(n[j:j + 1]))
            out.append(float(jnp.max(g)))
            rows.append(np.asarray(row[0]))
    return np.asarray(out), np.stack(rows)


def logit_err(rows, ref_rows):
    """The widest relative L2 gap between a row of logits and the
    reference's."""
    d = np.linalg.norm(rows.astype(np.float64) - ref_rows, axis=1)
    return float(np.max(d / np.linalg.norm(ref_rows.astype(np.float64),
                                            axis=1)))


# the reference in the program's place ((dtype, weight matmul) by name):
# the control, one step below the bfloat16 matmul operands the
# configuration states; and bfloat16 throughout, read for the record
CONTROLS = {"control": ("bfloat16", ref.fp8_matmul),
            "control_bf16": ("bfloat16", ref.matmul)}


def control_picks(jax, cfg, params, seq, first, name):
    """A control's own argmax at every position and its logits at each
    row's last prompt token."""
    jnp = jax.numpy
    dtype, mm = CONTROLS[name]
    fn = jax.jit(ref.picks(cfg, getattr(jnp, dtype), mm))
    out = [fn(params, jnp.asarray(seq[j:j + 1]), jnp.asarray(first[j:j + 1]))
           for j in range(seq.shape[0])]
    return (np.concatenate([np.asarray(a) for a, _ in out]),
            np.concatenate([np.asarray(r) for _, r in out]))


def checked(server, results, rids, rows):
    """(numbers, check rows) of the served outputs and the prefill logits
    against the reference."""
    if not rids:
        return {"served_gap": None, "prefill_logit_err": None,
                "wrong_length": None, "checked_tokens": 0}, None
    seq, first, n = check_rows(server, results, rids)
    wrong = int(sum(len(results[r]) != server.nout[r] for r in rids))
    gaps, ref_rows = reference_gaps(server.jax, server.cfg, server.params,
                                    seq, first, n)
    return {"served_gap": float(np.max(gaps)),
            "prefill_logit_err": logit_err(rows, ref_rows),
            "wrong_length": wrong, "checked_tokens": int(n.sum())}, \
        (seq, first, n, ref_rows)


AT_LEAST = ("checked_tokens",)


def run(jax, cell, cfg, traffic, limits, *, seed, seconds, trace, t_start,
        devs, peaks, per_layer, declared):
    sv = Server(jax, cfg, traffic, seed, seconds)
    sv.warm()
    watch = common.WindowWatch(jax, freeze=True)
    setup_s = time.perf_counter() - t_start
    prof = None
    if trace:
        prof = common.work_dir("trace", cell["name"])
        jax.profiler.start_trace(prof)
    with jax.profiler.TraceAnnotation("bench.window"), watch:
        w = sv.window(seconds)
    if trace:
        jax.profiler.stop_trace()
    # the engine's per-request records, before finish() frees it
    engine = sv.engine.snapshot()
    device = common.device_info(devs)
    ttft, itl, tokens = latency_metrics(w, sv.due, seconds)
    nums, _ = checked(sv, *sv.finish(seed))
    correct, table = common.judge([
        (k, nums[k], lim) + (("min",) if k in AT_LEAST else ())
        for k, lim in limits.items()])
    late = np.asarray(w["late"]) if w["late"] else np.zeros(1)
    print(f"generator lateness: median {np.median(late) * 1e3:.3f} ms, "
          f"max {late.max() * 1e3:.3f} ms over {len(w['late'])} "
          f"submissions; {w['submitted']} requests due, "
          f"{len(ttft)} timed, {tokens} tokens in {seconds} s; longest "
          f"admit {w['longest']['admit'] * 1e3:.3f} ms, step "
          f"{w['longest']['step'] * 1e3:.3f} ms", flush=True)
    print(watch.line(), flush=True)
    print("readings not compared: " + ", ".join(
        f"{k} {v!r}" for k, v in nums.items() if k not in limits),
        flush=True)
    print("latency ms: ttft p50 %.3f p90 %.3f p95 %.3f mean %.3f; "
          "itl p50 %.3f p95 %.3f" % tuple(
              1e3 * x for x in (common.quantile(ttft, 0.5),
                                common.quantile(ttft, 0.9),
                                common.quantile(ttft, 0.95), np.mean(ttft),
                                common.quantile(itl, 0.5),
                                common.quantile(itl, 0.95))), flush=True)
    result = {"correct": bool(correct), "attempted": w["submitted"],
              "failed": 0, "device": device}
    if not trace:
        result["metrics"] = {
            "serve_itl_p95_ms": {"value": 1e3 * common.quantile(itl, 0.95),
                                 "unit": "ms"},
            "serve_tokens_per_s": {"value": tokens / seconds,
                                   "unit": "tokens/s"},
            "setup_s": {"value": setup_s, "unit": "s"}}
        return result, table
    from bench import trace as tr_mod
    ex = tr_mod.extract(common.newest_trace(prof), {})
    red = tr_mod.reduce(ex, {}, declared["MODULES"])
    busy = [d["busy_ns"] for d in red["devices"].values()]
    result["device"]["busy_s"] = float(np.mean(busy)) / 1e9
    result["device"]["window_s"] = red["window_ns"] / 1e9
    result["breakdown"] = {"device_ops": red["device_ops"],
                           "idle_gaps": red["idle_gaps"]}
    ctx = {"cfg": cfg, "traffic": traffic, "peaks": peaks,
           "chips": len(devs), "reduced": red, "extract": ex,
           "engine": engine, "ttft": ttft, "counts": window_counts(w, sv)}
    result["metrics"] = common.read_metrics(per_layer, ctx)
    common.clear_dir(prof)
    return result, table


def window_counts(w, sv):
    """What the window served, for the FLOP and byte counts: each prefill
    by its prompt length, and each decode step by the context of every
    live slot."""
    prefills = [int(sv.plen[r]) for r in w["first"]]
    contexts = []  # per decode step: each live slot's attended positions
    steps = {}
    for rid, ts in w["times"].items():
        for j, t in enumerate(ts[1:], start=1):
            steps.setdefault(t, []).append(int(sv.plen[rid]) + j)
    for t in sorted(steps):
        contexts.append(steps[t])
    return {"prefill_lengths": prefills, "decode_contexts": contexts}


# --------------------------------------------------- readings for limits


def control_readings(jax, cfg, traffic, seed, *, sound, control, faults):
    """Yield (kind, numbers) for one seed at the cell's load over a short
    window: the program, the controls on the same served sequences, and
    the faults planted in the program."""
    seconds = traffic["check_window_s"]
    sv = Server(jax, cfg, traffic, seed, seconds)
    sv.warm()
    sv.window(seconds)
    sv.drain()
    nums, rows = checked(sv, *sv.finish(seed))
    if sound:
        yield "sound", nums
    if control and rows is not None:
        seq, first, n, ref_rows = rows
        for name in CONTROLS:
            cand, c_rows = control_picks(jax, cfg, sv.params, seq, first,
                                         name)
            gaps, _ = reference_gaps(jax, cfg, sv.params, seq, first, n,
                                     cand=cand)
            yield name, {"served_gap": float(np.max(gaps)),
                         "prefill_logit_err": logit_err(c_rows, ref_rows),
                         "checked_tokens": int(n.sum())}
    del sv
    gc.collect()
    if not faults:
        return
    for name, (patch, wrap) in SERVE_FAULTS.items():
        with patch():
            sv = Server(jax, cfg, traffic, seed, seconds, model_wrap=wrap)
            sv.warm()
            sv.window(seconds)
            sv.drain()
            finished = sv.finish(seed)
        nums, _ = checked(sv, *finished)
        yield name, nums
        del sv
        gc.collect()


@contextlib.contextmanager
def altered_tokens():
    """Every sampled token replaced by its successor in the vocabulary,
    where the engine produces it."""
    from repro.serving import engine
    real = engine.sample_token

    def sample_token(logits, rng, temperature=0.0, vocab_size=None):
        return (real(logits, rng, temperature, vocab_size) + 1) % vocab_size
    engine.sample_token = sample_token
    try:
        yield
    finally:
        engine.sample_token = real


def stale_cache(model):
    """A decode step that returns the cache it was given, unchanged."""
    def decode_step(params, caches, tokens, index):
        logits, _ = model.decode_step(params, caches, tokens, index)
        return logits, caches
    return dataclasses.replace(model, decode_step=decode_step)


# the faults a serving cell can have: (patch of the engine, model wrapper)
SERVE_FAULTS = {"altered_token": (altered_tokens, None),
                "stale_cache": (contextlib.nullcontext, stale_cache)}
