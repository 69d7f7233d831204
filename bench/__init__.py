"""On-chip benchmark of the BrePartition retrieval path.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once on the accelerator it finds and
prints one JSON result line.  Everything a cell needs is found by name:
``configs/<config>.json`` (the deployment), ``traffic/<mix>.json`` (the
traffic's parameters, read by ``drivers/<driver>.py``) and
``metrics/<metric>.py`` (one reader per metric).
"""
