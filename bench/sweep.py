"""The one-off knee sweep of a serving cell: the cell's traffic at rising
fixed rates, one window each on one engine, and per rate its tails, its
tokens per second and whether the backlog grew through the window. The
knee is the highest rate whose backlog does not grow and whose p95 time
to first token meets ``--ttft-limit-ms``; the cell's traffic file then
states 0.8 times it as a number, so no run sweeps.

    python3 bench/sweep.py --workload serve-16l.poisson --seed 1 \\
        --seconds 20 --rates 2 4 6 8 10 12 14
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import common  # noqa: E402

# a backlog "grows" when the queue in the last quarter of the window
# averages this many requests more than in the second quarter
GROWTH = 2.0


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--ttft-limit-ms", type=float, default=1000.0)
    args = ap.parse_args(argv)
    cell, cfg, traffic, _ = common.find_cell(args.workload)
    import jax
    common.require_chips(jax, cell["chips"])
    common.use_compile_cache(jax)
    from bench import gen, program, serve  # noqa: F401
    import numpy as np
    sv = serve.Server(jax, cfg, traffic, args.seed, args.seconds)
    sv.warm()
    for rate in args.rates:
        tr = dict(traffic, rate_per_s=rate)
        (sv.due, sv.plen, sv.nout), rng = gen.open_loop(tr, args.seed,
                                                         args.seconds)
        sv.prompts = [rng.integers(0, cfg["vocab_size"], n, dtype=np.int32)
                      for n in sv.plen]
        ticks = []
        w = sv.window(args.seconds,
                      on_tick=lambda t, q, live: ticks.append((t, q)))
        ttft, itl, tokens = serve.latency_metrics(w, sv.due, args.seconds)
        T = args.seconds
        q2 = [q for t, q in ticks if T / 4 <= t < T / 2]
        q4 = [q for t, q in ticks if t >= 3 * T / 4]
        growth = (np.mean(q4) if q4 else 0.0) - (np.mean(q2) if q2 else 0.0)
        print(json.dumps({
            "rate_per_s": rate, "requests": w["submitted"],
            "ttft_p90_ms": 1e3 * common.quantile(ttft, 0.90),
            "ttft_p95_ms": 1e3 * common.quantile(ttft, 0.95),
            "itl_p95_ms": 1e3 * common.quantile(itl, 0.95),
            "tokens_per_s": tokens / T,
            "queue_q2": float(np.mean(q2)) if q2 else 0.0,
            "queue_q4": float(np.mean(q4)) if q4 else 0.0,
            "sustained": bool(growth <= GROWTH
                              and 1e3 * common.quantile(ttft, 0.95)
                              <= args.ttft_limit_ms)}), flush=True)
        e = sv.engine
        e.queue.clear()
        while e.live_slots():
            e.step()
        e.results.clear()
        e.reset()


if __name__ == "__main__":
    main()
