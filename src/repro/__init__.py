"""repro — BrePartition reproduction (core search, kernels, serving, dist)."""
