"""The chip's peak bytes in use after the window, set-up included, in GB
(1e9 bytes)."""


def read(run):
    peak = run["memory_peak_bytes"]
    return None if not peak else peak / 1e9
