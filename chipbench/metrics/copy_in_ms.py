"""Host time putting each launch's padded planes on the device (the
program's ``serve.core.copy_in`` span inside the launch wall); ms per
request, from the traced run."""
from chipbench import spans


def read(record, trace):
    return spans.ms_per_request("serve.core.copy_in", record, trace)
