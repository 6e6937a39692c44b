"""Host time admitting jobs in ``SolverMux.submit`` (the program's
``serve.mux.admit`` span): the arguments to arrays, the finite admission
scan, enqueue; ms per request, from the traced run."""
from chipbench import spans


def read(record, trace):
    return spans.ms_per_request("serve.mux.admit", record, trace)
