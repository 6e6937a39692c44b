"""Host time in the call of the jitted entry point until it returns (the
program's ``serve.core.execute`` span inside the launch wall: dispatch,
not the kernel's completion); ms per request, from the traced run."""
from chipbench import spans


def read(record, trace):
    return spans.ms_per_request("serve.core.execute", record, trace)
