"""Share of its roofline that the refine phase reaches.

The least refine work of every launch the window's microbatches made
(bench/work.py: each distinct candidate row read once), over the device
time of the operations under the program's ``bp.refine`` scope in the
window's own trace (bench/phases.py): the candidate gather, the refine
kernel and the top-k, so this reads at or below
``kernel.refine_roofline``.
"""

from bench import phases


def read(run):
    return phases.roofline_share(run, "refine", ("bp.refine",))
