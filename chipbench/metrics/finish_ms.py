"""Host time accounting launches in ``SolverMux._supervise`` once a call
returned (the program's ``serve.mux.finish`` span): the finite check,
launch and job records, scatter, the ``flush`` event; ms per request,
from the traced run."""
from chipbench import spans


def read(record, trace):
    return spans.ms_per_request("serve.mux.finish", record, trace)
