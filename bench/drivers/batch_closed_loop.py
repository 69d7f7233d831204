"""One closed-loop caller of ``core/search.knn_batch``, with no service.

The paper's §7 protocol: a single caller sends ``rows_per_request`` query
rows per call, waits for the answer, and sends the next call at once, with
no think time, until the window closes; the call in flight then finishes.
Each call is ``knn_batch(index, rows, k, return_stats=True)``: the search
library's own budget ladder, the path a kNN-LM hook without a service
takes.  Set-up makes one call on the pool's last rows, which compiles
every program the window launches (the escalated budget too).

The window record keeps the service driver's keys, so its readers read
this cell unchanged: one request and one step per call, the launches in
``counters["launches"]`` and ``max_batch`` the rows of a call.  Each step
also keeps the call's wall time ``wall_s`` and its launches' device waits
``wait_s`` (``BatchStats.launches``), where the program records them.
The call in flight at the close is synchronous, so ``grace_s`` bounds
nothing here.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np


@dataclasses.dataclass
class State:
    index: object
    queries: np.ndarray
    k: int
    rows: int


def _call(state: State, ys: np.ndarray):
    from repro.core import search

    return search.knn_batch(state.index, ys, state.k, return_stats=True)


def _launches(stats) -> int:
    """Launches of one call.  A program whose ``BatchStats`` keeps no
    per-launch records counts them from its escalation counters."""
    records = getattr(stats, "launches", None)
    if records is not None:
        return len(records)
    return stats.escalations + 1 + int(stats.escalated_to_scan)


def setup(index, queries: np.ndarray, config: dict, traffic: dict,
          log=print) -> State:
    if int(traffic["clients"]) != 1:
        raise ValueError("batch_closed_loop drives one caller")
    state = State(index=index, queries=queries, k=int(config["k"]),
                  rows=int(traffic["rows_per_request"]))
    t0 = time.perf_counter()
    res, stats = _call(state, queries[len(queries) - state.rows:])
    bad = int((~np.asarray(res.exact)).sum())
    log(f"setup: warm-up call {time.perf_counter() - t0} s ({state.rows} "
        f"rows, {_launches(stats)} launches, final budget "
        f"{stats.budget_final}, not exact: {bad})")
    return state


def window(state: State, seconds: float) -> dict:
    """Run the closed loop for ``seconds``; every call of the window."""
    pool = len(state.queries) - state.rows
    requests, steps = [], []
    launches = 0
    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        close = t0 + seconds
        sent = 0
        while time.perf_counter() < close:
            first = (sent * state.rows) % (pool - state.rows)
            sent += 1
            qi = list(range(first, first + state.rows))
            submit = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.step"):
                res, stats = _call(state, state.queries[qi])
                ids, dists = np.asarray(res.ids), np.asarray(res.dists)
                exact = bool(np.asarray(res.exact).all())
            answer = time.perf_counter()
            requests.append({"queries": qi, "submit": submit,
                             "answer": answer,
                             "quality": "exact" if exact else "partial",
                             "ids": ids, "dists": dists})
            step = {"rows": len(qi), "launches": _launches(stats),
                    "budgets": [stats.budget_final], "queries": qi}
            if getattr(stats, "launches", None):
                step.update(wall_s=answer - submit,
                            wait_s=sum(x["wait_s"] for x in stats.launches))
            launches += step["launches"]
            steps.append(step)
    return {"t0": t0, "close": close,
            "last": requests[-1]["answer"] if requests else None,
            "requests": requests, "steps": steps,
            "max_batch": state.rows, "live_n": int(state.index.n),
            "counters": {"launches": launches}}


def replay(state: State, record: dict) -> list:
    """The window's last call again, launch by launch, through the search
    entry point ``knn_batch`` calls: per launch its budget, query rows and
    each query's Theorem-3 union size."""
    from repro.core import search

    step = record["steps"][-1]
    ys = state.queries[step["queries"]]
    q = ys.shape[0]
    launches = []
    budget = search.default_budget(state.index, state.k)
    for final in [None] + step["budgets"]:
        if final is not None:
            if final == budget:
                continue
            budget = final
        with jax.profiler.TraceAnnotation("bench.replay"):
            res = search.knn_search_batch(state.index, ys, state.k, budget,
                                          validate=False)
            jax.block_until_ready(res)
        launches.append({"budget": int(budget), "q": q,
                         "num_candidates":
                             np.asarray(res.num_candidates).tolist()})
        if bool(np.asarray(res.exact).all()):
            break
    return launches


def release(state: State) -> None:
    state.index = None
