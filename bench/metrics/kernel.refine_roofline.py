"""Share of its roofline that the refine kernels reach.

The least time of the refine work that the replayed launches required
(bench/work.py: each distinct candidate row read once; bytes-bound at
these sizes), over the device time of these kernels' trace events in the
replay.
"""

from bench import traces, work

KERNELS = ("bregman_refine_batch", "bregman_refine_batch_quant")


def read(run):
    rp = run["replay"]
    if not rp or not run["peaks"]:
        return None
    seconds = traces.kernel_seconds(rp["ops"], KERNELS)
    if seconds <= 0:
        return None
    least = sum(work.launch_seconds(launch, run["shape"], run["peaks"])
                ["refine"] for launch in rp["launches"])
    return 100.0 * least / seconds
