"""Solves done inside the window, divided by the window's seconds."""


def read(record, trace):
    return record["solves_in_window"] / record["window_s"]
