"""Set-up time: process start to the start of the measured window."""


def read(record, trace):
    return record["setup_s"]
