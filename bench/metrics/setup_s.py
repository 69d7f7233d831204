"""Seconds from the start of the process until the window opens."""


def read(run):
    return run["setup_s"]
