"""Share of the window's search launches whose refine read the table in
shared passes, each stored row once for every query of the launch, rather
than gathering each query's candidate rows.

The service counts them (``dense_refines`` over ``launches``).  A cell
without that counter, the ``knn_batch`` loop, reads the replayed call's
launches through the program's own path rule
(``core/search.dense_refine``).  A program with neither reads nothing.
"""


def read(run):
    c = run["window"]["counters"]
    if "dense_refines" in c:
        launches = c.get("launches")
        return 100.0 * c["dense_refines"] / launches if launches else None
    replay = run.get("replay")
    if not replay or not replay["launches"]:
        return None
    try:
        from repro.core.search import dense_refine
    except ImportError:
        return None
    n = run["shape"]["n"]
    flags = [dense_refine(x["q"], x["budget"], n) for x in replay["launches"]]
    return 100.0 * sum(flags) / len(flags)
