"""Share of its roofline that the filter and prune phases reach.

The least time of the filter and Theorem-3 prune work of every launch the
window's microbatches made (bench/work.py), over the device time of the
operations under the program's ``bp.filter`` and ``bp.prune`` scopes in
the window's own trace (bench/phases.py).  Every operation of the phases
counts, the fusions and loops around the kernels too, so this reads at or
below ``kernel.filter_prune_roofline``.
"""

from bench import phases


def read(run):
    return phases.roofline_share(run, "filter_prune",
                                 ("bp.filter", "bp.prune"))
