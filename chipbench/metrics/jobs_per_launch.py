"""Jobs a launch carried: the jobs that the window's launches served
(``jobs_launched_in_window``, filler lanes left out) over the window's
device events of the served kernel, the TPU custom call that each launch
runs once.  None in a run that was not traced, or whose window holds no
such event."""
from chipbench import trace as tr


def served_kernel(name: str, stats: list[str]) -> bool:
    """A served Pallas kernel's device op: its short name ends in the
    custom call's target (``trace.short_name``)."""
    return name.endswith(" tpu_custom_call")


def read(record, trace):
    if trace is None:
        return None
    _, events = tr.kernel(trace, served_kernel)
    if not events:
        return None
    return record["jobs_launched_in_window"] / events
