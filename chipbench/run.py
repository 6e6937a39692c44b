#!/usr/bin/env python3
"""Run one benchmark cell once on the chip and print its result line.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Imports the program from this checkout's
``src``, keeps JAX's persistent compilation cache inside the checkout,
and exits non-zero
without a result line when JAX sees no TPU, fewer chips than the cell
needs, or Pallas would run in interpret mode.  The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, ``breakdown`` when traced, and ``checks`` last:
each number compared with its limit); the checks are also the last lines
of standard error.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SRC = os.path.join(ROOT, "src")
# JAX's persistent compilation cache: a fixed path inside the checkout,
# given to the program through the variable it reads
CACHE_DIR = os.path.join(ROOT, "chipbench", "out", "jax_cache")


def fail(msg: str, code: int = 1) -> None:
    print(f"chipbench: FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(code)


def import_repro():
    """Import the program from this checkout's ``src`` only."""
    sys.path.insert(0, SRC)
    try:
        import repro
    except ImportError as e:
        fail(f"cannot import the program from {SRC}: {e}")
    where = [os.path.abspath(p) for p in repro.__path__]
    if not all(p.startswith(SRC + os.sep) for p in where):
        fail(f"repro resolved outside this checkout: {where}")
    return repro


def setup_jax(chips: int):
    """Compile cache, then the device checks; returns nothing on success.
    Call before anything imports JAX, which reads the cache directory
    from the environment once.  The directory is set here whatever the
    environment held, so that two checkouts never share a cache."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax
    # the kernels compile in well under a second each: cache them all, so
    # that only the first run of a cell in a checkout compiles
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devices = jax.devices()
    if devices[0].platform != "tpu":
        fail(f"JAX's default device is {devices[0].platform!r}, not a TPU")
    if len(devices) < chips:
        fail(f"the cell needs {chips} chips; JAX sees {len(devices)}")
    from repro.kernels.common import interpret_default
    try:
        interpret = interpret_default()
    except RuntimeError as e:
        fail(str(e))
    if interpret:
        fail("Pallas kernels would run in interpret mode")


def emit(result: dict) -> None:
    info = result.pop("info")
    for k, v in info.items():
        print(f"{k}: {json.dumps(v)}", flush=True)
    checks = result.pop("checks")
    result["checks"] = checks          # the last key of the result line
    for k, c in checks.items():
        print(f"check {k}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    # the benchmark's modules import as ``chipbench.*`` from the root
    sys.path[:] = [p for p in sys.path
                   if os.path.abspath(p or ".") != os.path.dirname(
                       os.path.abspath(__file__))]
    sys.path.insert(0, ROOT)
    from chipbench import harness
    try:
        bench = harness.load_benchmark()
        entry, _, _ = harness.cell(bench, args.workload)
    except (OSError, ValueError, harness.BenchError) as e:
        fail(str(e))
    import_repro()
    setup_jax(entry["chips"])
    try:
        result = harness.run_cell(bench, args.workload, args.seed,
                                  args.seconds, bool(args.trace), T_START)
    except harness.BenchError as e:
        fail(str(e))
    emit(result)


if __name__ == "__main__":
    main()
