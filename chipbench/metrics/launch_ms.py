"""Launch walls per request: the sum of ``LaunchRecord.measured`` (stack,
pad, copy in, kernel, copy out, sync) over the run, in ms per request."""


def read(record, trace):
    if not record["requests"]:
        return None
    return record["launch_s"] / record["requests"] * 1e3
