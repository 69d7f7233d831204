"""Query rows the service answered per search launch in the window."""


def read(run):
    w = run["window"]
    launches = w["counters"]["launches"]
    rows = sum(s["rows"] for s in w["steps"])
    return rows / launches if launches else None
