"""Share of its roofline that the whole replayed microbatch reaches.

The least time of all the search work the replayed launches required
(bench/work.py: filter, prune and refine), over the device's busy time in
the replay: every operation on the path counts, whichever kernel or
fusion runs it, so a phase moved out of the named kernels still shows.
"""

from bench import work


def read(run):
    rp = run["replay"]
    if not rp or not run["peaks"] or rp["busy_s"] <= 0:
        return None
    least = sum(sum(work.launch_seconds(launch, run["shape"],
                                        run["peaks"]).values())
                for launch in rp["launches"])
    return 100.0 * least / rp["busy_s"]
