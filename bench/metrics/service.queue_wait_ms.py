"""Mean time a request of the window waited in the service, from submit
to the first launch of its microbatch (the service's ``queue_s`` and
``microbatch_requests`` counters)."""


def read(run):
    c = run["window"]["counters"]
    if not c.get("microbatch_requests"):
        return None
    return 1e3 * c["queue_s"] / c["microbatch_requests"]
