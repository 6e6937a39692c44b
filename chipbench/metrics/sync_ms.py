"""Host time waiting for each launch's answer and copying it back (the
program's ``serve.core.copy_out`` span inside the launch wall); ms per
request, from the traced run."""
from chipbench import spans


def read(record, trace):
    return spans.ms_per_request("serve.core.copy_out", record, trace)
