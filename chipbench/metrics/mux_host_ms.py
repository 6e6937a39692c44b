"""Host time in the mux per request: the benchmark's spans around
``submit``, ``poll`` and the drain's ``run``, less the launch walls
(``LaunchRecord.measured``) inside them, in ms per request."""


def read(record, trace):
    if not record["requests"]:
        return None
    spans = record["span_s"]
    host = sum(spans.get(k, 0.0) for k in ("submit", "poll", "drain"))
    return (host - record["launch_s"]) / record["requests"] * 1e3
