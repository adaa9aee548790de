#!/usr/bin/env python3
"""Rate sweep of one cell, for choosing its fixed reactive rate.

    python bench/sweep.py --workload <cell> --seed <n> --seconds <s> \\
        --rates 1,2,3,4

One process: set-up and warm-up once, then one window per rate with the
cell's agents running, each followed by its post-roll.  For each rate it
prints one JSON line: reactive flows in the window, TTFT p50 and p90 over
them, the median TTFT of the window's first and second halves, the
reactive backlog (sent, no first token yet) at the window's start and end,
the reactive TPOT p90 and the proactive tokens per second.  The knee is
the highest rate whose backlog does not grow across the window; a cell
offers about four fifths of it.
"""
from __future__ import annotations

import json
import os
import sys

sys.path[:0] = [os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import loadgen, run  # noqa: E402


def summary(rec: dict, rate: float) -> dict:
    ws, we = rec["ws"], rec["we"]
    mid = (ws + we) / 2
    rea = [f for f in rec["flows"] if f["in_window"]
           and f["kind"] == "reactive"]
    ttft = [(f["walls"][0] - f["intended"]) * 1e3 for f in rea if f["walls"]]
    halves = [[(f["walls"][0] - f["intended"]) * 1e3 for f in rea
               if f["walls"] and (f["intended"] < mid) == first]
              for first in (True, False)]
    tpot = [(f["walls"][-1] - f["walls"][0]) / (len(f["walls"]) - 1) * 1e3
            for f in rea if len(f["walls"]) > 1]
    pro = sum(1 for f in rec["flows"] if f["kind"] == "proactive"
              for w in f["walls"] if ws <= w < we)
    return {"rate": rate, "reactive_in_window": len(rea),
            "unfinished": sum(f["status"] != "completed" for f in rea),
            "ttft_p50_ms": loadgen.percentile(ttft, 50),
            "ttft_p90_ms": loadgen.percentile(ttft, 90),
            "ttft_p50_first_half_ms": loadgen.percentile(halves[0], 50),
            "ttft_p50_second_half_ms": loadgen.percentile(halves[1], 50),
            "backlog_start": rec["counters"].get("backlog_start"),
            "backlog_end": rec["counters"].get("backlog_end"),
            "tpot_p90_ms": loadgen.percentile(tpot, 90),
            "proactive_tokens_per_s": pro / (we - ws)}


def main(argv=None, *, bench=None, data_dir=run.BENCH_DIR,
         require_tpu=True) -> int:
    argv = list(sys.argv[1:] if argv is None else argv)
    i = argv.index("--rates")
    rates = [float(r) for r in argv[i + 1].split(",")]
    del argv[i:i + 2]
    args = run.parse(argv + ["--rate", str(rates[0])])
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(run.ROOT,
                                                           ".jax_cache")
    log = lambda m: print(m, file=sys.stderr, flush=True)  # noqa: E731
    if bench is None:
        bench = run.read_json(os.path.join(run.ROOT, "BENCHMARK.json"))
    try:
        ctx = run.prepare(args, bench, data_dir, require_tpu, log)
    except run.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    from repro.launch.frontend import ServingFrontend
    ctx.fe.close(timeout=300)
    prev = ctx.gen
    for k, rate in enumerate(rates):
        gen = loadgen.Traffic(ctx.traffic, rate, ctx.agents,
                              ctx.cfg.vocab_size, args.seed + k,
                              args.seconds)
        # keep drawing first tokens from one sequence: no prefix hits
        gen._first, gen._n_first = prev._first, prev._n_first
        fe = ServingFrontend(ctx.eng,
                             max_buffered_tokens=run.MAX_BUFFERED).start()
        rec = run.drive(fe, ctx.eng, gen, args.seconds,
                        float(ctx.traffic["drain_limit_s"]), log=log)
        print(json.dumps(summary(rec, rate)), flush=True)
        prev = gen
    return 0


if __name__ == "__main__":
    sys.exit(main())
