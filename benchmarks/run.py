"""Benchmark driver: one entry per paper table/figure or subsystem.

  fig11/21/22  control-overhead analytics   bench_control_overhead
  fig2         masking utilization          bench_masking_util
  fig19        mechanism stack (timed)      bench_mechanisms
  fig16        latency-optimized kernels    bench_latency
  fig17        throughput-optimized         bench_throughput
  roofline     3-term table from dry-run    bench_roofline
  serving      mixed-traffic SLO (mux)      bench_pipelines.run_slo
  variants     variant-dispatch sweep       bench_pipelines.run_variants

Prints ``name,us_per_call,derived,unit`` CSV.  ``--only <prefix>``
filters.
``--json-out FILE`` additionally persists the run as JSON — rows plus
the per-kernel/per-variant dispatch counts, model FLOPs and wall-clock
from the ``variants`` entry — the ``BENCH_pipelines.json`` perf baseline
committed at the repo root and checked by CI's bench-smoke step
(see benchmarks.check_bench_json)."""
from __future__ import annotations

import argparse
import json
import sys
import time

# 8 virtual CPU devices on a CPU-only run (merged into XLA_FLAGS before
# the first jax import; an explicit device count in the env is respected)
# so the serve_slo entry can sweep mesh sizes up to 8
from repro.launch.xla_env import force_host_device_count, setup_compile_cache

force_host_device_count(8)

from benchmarks import (bench_control_overhead, bench_latency,
                        bench_masking_util, bench_mechanisms,
                        bench_pipelines, bench_roofline, bench_throughput,
                        common)

ENTRIES = [
    ("control_overhead", bench_control_overhead.run),
    ("masking_util", bench_masking_util.run),
    ("mechanisms", bench_mechanisms.run),
    ("pipelines", bench_pipelines.run),
    ("variants", bench_pipelines.run_variants),
    ("serve_slo", bench_pipelines.run_slo),
    ("latency", bench_latency.run),
    ("throughput", bench_throughput.run),
    ("roofline", bench_roofline.run),
]


def json_payload(ran: list[str]) -> dict:
    """Fold the collected rows + variant records into the persisted
    baseline structure (schema 1)."""
    counts: dict[str, dict[str, int]] = {}
    for rec in common.VARIANTS:
        per = counts.setdefault(rec["pipeline"], {})
        per[rec["variant"]] = per.get(rec["variant"], 0) \
            + int(rec["dispatches"])
    return {
        "schema": 1,
        "entries": ran,
        # ratio rows (cost-model drift) live far below 1.0 in interpret
        # mode — 2-decimal rounding would flatten them to 0.0
        "rows": [{"name": n,
                  "us_per_call": round(us, 6 if u == "ratio" else 2),
                  "derived": d, "unit": u}
                 for n, us, d, u in common.ROWS],
        "variants": common.VARIANTS,
        "dispatch_counts": counts,
        "sharded": common.SHARDED,
        "decode": common.DECODE,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated entry-name substrings, e.g. "
                         "'variants,serve_slo'")
    ap.add_argument("--json-out", default=None, metavar="FILE",
                    help="write rows + variant dispatch/flops records "
                         "as JSON (the BENCH_pipelines.json baseline)")
    args = ap.parse_args(argv)
    setup_compile_cache()
    print("name,us_per_call,derived,unit")
    t0 = time.time()
    ran = []
    for name, fn in ENTRIES:
        if args.only and not any(tok and tok in name
                                 for tok in args.only.split(",")):
            continue
        fn()
        ran.append(name)
    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)
    if args.json_out:
        with open(args.json_out, "w") as f:
            json.dump(json_payload(ran), f, indent=1, sort_keys=True)
            f.write("\n")
        print(f"# wrote {args.json_out}", file=sys.stderr)


if __name__ == "__main__":
    main()
