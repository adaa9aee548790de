"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (harness contract), where `derived`
is each figure's headline number, plus the roofline table if dry-run
artifacts are present.

    PYTHONPATH=src python -m benchmarks.run [--quick]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import figures, hetero, loadgen  # noqa: E402
from benchmarks.roofline import table as roofline_table  # noqa: E402
from repro.launch.compile_cache import configure_compile_cache  # noqa: E402

BENCHES = [
    ("fig_op_affinity", figures.bench_op_affinity),
    ("fig3_contention", figures.bench_contention),
    ("sec3.2_batching", figures.bench_batching),
    ("fig4_coscheduling", figures.bench_coscheduling),
    ("fig6_proactive_only", figures.bench_proactive_only),
    ("fig7_mixed", figures.bench_mixed),
    ("ablation_mechanisms", figures.bench_ablation),
    ("real_decode_batching", figures.bench_real_decode_batching),
    ("decode_throughput", figures.bench_decode_throughput),
    ("prefill_throughput", figures.bench_prefill_throughput),
    ("prefix_reuse", figures.bench_prefix_reuse),
    ("reactive_latency", figures.bench_reactive_latency),
    ("serving_slo", loadgen.bench_serving),
    ("hetero_overlap", hetero.bench_hetero),
]


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="skip the slow end-to-end sweeps")
    ap.add_argument("--only", default=None,
                    help="run a single named benchmark (e.g. "
                         "decode_throughput for the BENCH_decode.json entry)")
    ap.add_argument("--out", default="experiments/bench")
    args = ap.parse_args(argv)
    configure_compile_cache()

    os.makedirs(args.out, exist_ok=True)
    if args.only is not None and args.only not in dict(BENCHES):
        raise SystemExit(f"unknown benchmark {args.only!r}; "
                         f"choose from {[n for n, _ in BENCHES]}")
    print("name,us_per_call,derived")
    for name, fn in BENCHES:
        if args.only is not None and name != args.only:
            continue
        if args.only is None and args.quick and name in (
                "fig6_proactive_only", "fig7_mixed", "ablation_mechanisms",
                "real_decode_batching", "decode_throughput",
                "prefill_throughput", "prefix_reuse", "reactive_latency",
                "serving_slo", "hetero_overlap"):
            continue
        t0 = time.time()
        rows, derived = fn()
        us = (time.time() - t0) * 1e6
        print(f"{name},{us:.0f},{derived:.4g}", flush=True)
        with open(os.path.join(args.out, name + ".json"), "w") as f:
            json.dump({"rows": rows, "derived": derived,
                       "us_per_call": us}, f, indent=2, default=float)

    # roofline (from dry-run artifacts, if present)
    t0 = time.time()
    try:
        rows, frac = roofline_table()
        if rows:
            us = (time.time() - t0) * 1e6
            print(f"roofline_table,{us:.0f},{frac:.4g}")
            with open(os.path.join(args.out, "roofline.json"), "w") as f:
                json.dump({"rows": rows, "derived": frac}, f, indent=2,
                          default=float)
    except Exception as e:  # dry-run not executed yet
        print(f"roofline_table,0,skipped({e})", file=sys.stderr)


if __name__ == "__main__":
    main()
