"""Share of its roofline that the filter and prune kernels reach.

The least time of the filter and Theorem-3 prune work that the replayed
launches required (bench/work.py: bytes-bound at these sizes), over the
device time of these kernels' trace events in the replay.
"""

from bench import traces, work

KERNELS = ("bregman_ub_matrix", "bregman_ub_matrix_quant",
           "bregman_filter_prune", "bregman_filter_prune_quant")


def read(run):
    rp = run["replay"]
    if not rp or not run["peaks"]:
        return None
    seconds = traces.kernel_seconds(rp["ops"], KERNELS)
    if seconds <= 0:
        return None
    least = sum(work.launch_seconds(launch, run["shape"], run["peaks"])
                ["filter_prune"] for launch in rp["launches"])
    return 100.0 * least / seconds
