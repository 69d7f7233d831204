"""Mean over the window's microbatches of the refine budget the answers
ran with, as a share of the live rows: the refine slots sized to the
largest Theorem-3 union."""


def read(run):
    w = run["window"]
    shares = [b / w["live_n"] for s in w["steps"] for b in s["budgets"][-1:]]
    return 100.0 * sum(shares) / len(shares) if shares else None
