"""Search launches per microbatch in the window: the first launch plus
every budget escalation of the host retry loop and the tier ladder.

A step of the service runs one microbatch per max_batch rows of one
(tenant, k, target_recall) group; the closed-loop mixes send one group.
"""

import math


def read(run):
    w = run["window"]
    batches = sum(math.ceil(s["rows"] / w["max_batch"]) for s in w["steps"])
    return w["counters"]["launches"] / batches if batches else None
