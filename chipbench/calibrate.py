#!/usr/bin/env python3
"""Readings that set a cell's rate and its correctness limit, on the chip.

    python chipbench/calibrate.py sweep   --workload W --rates 4,6,8 --seconds 5
    python chipbench/calibrate.py seeds   --workload W --seeds 1,2,3 --seconds 3
    python chipbench/calibrate.py control --workload W --seeds 1,2,3

``sweep`` runs an open-loop cell at each rate in turn (one process, the
cell's own sizes) and prints latency and generator lateness per rate:
the knee is the highest rate at which lateness stays bounded through the
window.  ``seeds`` runs the cell itself on each seed with a short window
and prints the number each run compares (the program's readings).
``control`` computes the job kind's control, the reference in the
precision below the program's, on the inputs a run of each seed serves,
and prints the same number (the control's readings).  Each line is one
JSON object.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path.insert(0, os.path.dirname(HERE))

from chipbench import harness, run  # noqa: E402


def out(**fields) -> None:
    print(json.dumps(fields), flush=True)


def sweep(bench, args) -> None:
    _, cfg, traffic = harness.cell(bench, args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        res = harness.run_cell(bench, args.workload, args.seed, args.seconds,
                               False, time.monotonic(),
                               traffic=dict(traffic, rate_per_s=rate))
        out(rate_per_s=rate, correct=res["correct"],
            metrics={k: v["value"] for k, v in res["metrics"].items()},
            **{k: res["info"][k] for k in ("requests", "lateness_max_s",
                                            "lateness_mean_s",
                                            "window_closed_late_s")})


def seeds(bench, args) -> None:
    for seed in (int(s) for s in args.seeds.split(",")):
        res = harness.run_cell(bench, args.workload, seed, args.seconds,
                               False, time.monotonic())
        out(seed=seed, correct=res["correct"], failed=res["failed"],
            compared=res["info"]["compared"],
            **{k: c["value"] for k, c in res["checks"].items()})


def control(bench, args) -> None:
    import numpy as np
    _, cfg, traffic = harness.cell(bench, args.workload)
    kind = harness.job_kind(cfg)
    for seed in (int(s) for s in args.seeds.split(",")):
        pool = harness.build_pool(kind, cfg, seed, int(traffic["pool"]))
        worst = 0.0
        for args_list in pool:
            want = kind.reference(cfg, args_list)
            got = kind.control(cfg, args_list)
            worst = max(worst, float(np.max(harness.rel_errors(got, want))))
        out(seed=seed, control_max_rel_err=worst)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("mode", choices=("sweep", "seeds", "control"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seeds", default="1,2,3")
    ap.add_argument("--rates", default="8")
    ap.add_argument("--seconds", type=float, default=3.0)
    args = ap.parse_args(argv)
    bench = harness.load_benchmark()
    entry, _, _ = harness.cell(bench, args.workload)
    run.import_repro()
    run.setup_jax(entry["chips"])
    {"sweep": sweep, "seeds": seeds, "control": control}[args.mode](bench,
                                                                    args)


if __name__ == "__main__":
    main()
