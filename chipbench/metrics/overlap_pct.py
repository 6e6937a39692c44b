"""Share of launches dispatched while an earlier launch's answer was
still ungathered: 100 × the program's ``serve.core.execute`` spans that
begin after an earlier ``execute`` whose ``serve.core.copy_out`` has not
begun yet, over every ``execute`` span of the traced run.  Answers are
gathered in launch order, so a count of launches not yet gathered says
which.  0 where each launch is gathered before the next is dispatched;
None in a run that was not traced, or whose trace holds no program
spans."""
from chipbench import spans
from chipbench import trace as tr

EXECUTE = "serve.core.execute"
COPY_OUT = "serve.core.copy_out"


def share(pd) -> float | None:
    """The share in percent over the whole trace ``pd``."""
    ungathered = overlapped = executes = 0
    for _, _, name in spans.host_events(pd, names={EXECUTE, COPY_OUT}):
        if name == EXECUTE:
            executes += 1
            overlapped += ungathered > 0
            ungathered += 1
        else:
            ungathered = max(0, ungathered - 1)
    return 100.0 * overlapped / executes if executes else None


def read(record, trace):
    if trace is None:
        return None
    path = tr.find_xplane(spans.TRACE_DIR)
    return None if path is None else share(tr.load(path))
