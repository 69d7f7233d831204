"""Host time per ``knn_batch`` call of the window: the call's wall time
less its launches' waits for the device (``BatchStats.launches``
``wait_s``, taken where the ``knn.wait`` spans open and close), averaged
over the calls.  Only the batch driver's steps carry these fields."""


def read(run):
    calls = [s for s in run["window"]["steps"] if "wall_s" in s]
    if not calls:
        return None
    return 1e3 * sum(s["wall_s"] - s["wait_s"] for s in calls) / len(calls)
