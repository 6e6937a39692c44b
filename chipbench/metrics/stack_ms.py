"""Host time preparing launches in ``SolverMux._launch`` (the program's
``serve.mux.stack`` span): variant resolve, one ``np.stack`` per
argument, filler padding; ms per request, from the traced run."""
from chipbench import spans


def read(record, trace):
    return spans.ms_per_request("serve.mux.stack", record, trace)
