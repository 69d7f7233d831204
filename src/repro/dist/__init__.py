"""repro.dist — sharding rules and the distributed execution substrates.

Submodules:

* :mod:`repro.dist.sharding` — logical-axis -> mesh-axis resolution
  (``spec_for_shape``), the ``constrain`` activation anchor, and mesh
  construction.
* :mod:`repro.dist.knn` — the sharded BrePartition search
  (``shard_index`` / ``distributed_knn``).
* :mod:`repro.dist.collective_matmul` — ring all-gather / reduce-scatter
  matmuls.
* :mod:`repro.dist.compression` — int8 gradient compression with error
  feedback.
* :mod:`repro.dist.pipeline` — microbatch pipeline-parallel schedule.
"""
