"""A kernel's share of its roofline: the least time the chip could take
for the jobs that the window's launches served, over the kernel's device
time in the trace.  Filler lanes of a padded launch are not work.  The
least time is the larger of operations over peak FLOP/s and bytes over
peak bytes/s, with operations and bytes per job from
``work/<kernel>.py``.  None where the trace holds no event of the kernel.

The kernel is found by ``match`` in the device ops' names; a window with
more than one distinct op that matches is refused, since its device time
could not be told apart."""
from chipbench import harness
from chipbench import trace as tr


def share(kernel: str, record, trace):
    if trace is None:
        return None
    w = harness.work(kernel)
    names = sorted(n for n, op in trace["ops"].items()
                   if w.match(n, op["stats"]))
    if len(names) > 1:
        raise harness.BenchError(
            f"{kernel}: {len(names)} device ops match its kernel ({names}); "
            f"its roofline needs the kernel named apart")
    seconds, events = tr.kernel(trace, w.match)
    jobs = record["jobs_launched_in_window"]
    if not events or seconds <= 0.0 or not jobs:
        return None
    pk = harness.peaks(record["device_kind"])
    flops, nbytes = w.per_lane(record["shapes"])
    least = max(jobs * flops / pk["flops_per_s"],
                jobs * nbytes / pk["bytes_per_s"])
    return 100.0 * least / seconds
