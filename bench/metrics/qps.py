"""Query rows answered exactly, over the time from the window's start to
its last answer (requests in flight at the close finish and count)."""


def read(run):
    w = run["window"]
    rows = sum(len(r["queries"]) for r in w["requests"]
               if r["quality"] == "exact")
    if not rows or w["last"] is None or w["last"] <= w["t0"]:
        return None
    return rows / (w["last"] - w["t0"])
