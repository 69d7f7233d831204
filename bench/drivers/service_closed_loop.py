"""Closed-loop clients of the retrieval service.

Each of ``clients`` callers sends a request of ``rows_per_request`` query
rows, waits for its answer, and sends the next at once, with no think
time, until the window closes; requests in flight then finish.  The
service runs as a deployment would drive it: ``register_tenant`` ->
``submit`` / ``step``.  Set-up sends one full round of the traffic, which
compiles every program the window launches (the escalated budget too)
and no other: ``warm()`` would also build the approximate tier's
program, which this traffic never runs.

Queries are rows of a held-out pool: the window cycles through all but
the pool's last round, which the warm-up round uses.
"""

from __future__ import annotations

import dataclasses
import time

import jax
import numpy as np

TENANT = "bench"


@dataclasses.dataclass
class State:
    service: object
    queries: np.ndarray
    k: int
    clients: int
    rows: int
    grace_s: float


def _service(config: dict):
    from repro.serve.retrieval import RetrievalService, ServiceConfig

    return RetrievalService(ServiceConfig(**config["service"]))


def setup(index, queries: np.ndarray, config: dict, traffic: dict,
          log=print) -> State:
    svc = _service(config)
    state = State(service=svc, queries=queries, k=int(config["k"]),
                  clients=int(traffic["clients"]),
                  rows=int(traffic["rows_per_request"]),
                  grace_s=float(traffic["grace_s"]))
    t0 = time.perf_counter()
    svc.register_tenant(TENANT, index)
    t1 = time.perf_counter()
    warm_rows = len(queries) - state.clients * state.rows
    tickets = [svc.submit(TENANT, _rows(state, warm_rows, c), state.k)
               for c in range(state.clients)]
    svc.run_until_drained()
    t2 = time.perf_counter()
    bad = [t.response.quality for t in tickets
           if not t.done or t.response.quality != "exact"]
    log(f"setup: register_tenant {t1 - t0} s, warm-up round {t2 - t1} s "
        f"({state.clients} requests, not exact: {bad})")
    return state


def _rows(state: State, start: int, c: int) -> np.ndarray:
    i = start + c * state.rows
    return state.queries[i:i + state.rows]


def window(state: State, seconds: float) -> dict:
    """Run the closed loop for ``seconds``; every request of the window."""
    svc = state.service
    pool = len(state.queries) - state.clients * state.rows
    per_round = state.clients * state.rows
    requests, steps, pending = [], [], {}
    sent = [0] * state.clients
    before = dict(svc.counters)

    def submit(c: int, now: float) -> None:
        first = (sent[c] * per_round + c * state.rows) % (pool - state.rows)
        sent[c] += 1
        qi = list(range(first, first + state.rows))
        with jax.profiler.TraceAnnotation("bench.submit"):
            ticket = svc.submit(TENANT, state.queries[qi], state.k)
        req = {"queries": qi, "submit": now, "answer": None,
               "quality": None, "ids": None, "dists": None,
               "uid": ticket.uid}
        requests.append(req)
        pending[c] = (ticket, req)

    with jax.profiler.TraceAnnotation("bench.window"):
        t0 = time.perf_counter()
        close = t0 + seconds
        for c in range(state.clients):
            submit(c, time.perf_counter())
        while pending and time.perf_counter() < close + state.grace_s:
            launches = svc.counters["launches"]
            with jax.profiler.TraceAnnotation("bench.step"):
                svc.step()
            now = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.answer"):
                done = []
                for c, (ticket, req) in list(pending.items()):
                    if not ticket.done:
                        continue
                    r = ticket.response
                    req.update(answer=now, quality=r.quality,
                               ids=np.asarray(r.ids), dists=np.asarray(r.dists),
                               budget=r.meta.get("budget"))
                    done.append(req)
                    del pending[c]
                    if now < close:
                        submit(c, time.perf_counter())
                done.sort(key=lambda q: q["uid"])
                steps.append({
                    "rows": sum(len(q["queries"]) for q in done),
                    "launches": svc.counters["launches"] - launches,
                    "budgets": sorted({q["budget"] for q in done
                                       if q["budget"] is not None}),
                    "queries": [i for q in done for i in q["queries"]]})
    answered = [q["answer"] for q in requests if q["answer"] is not None]
    return {"t0": t0, "close": close,
            "last": max(answered) if answered else None,
            "requests": requests, "steps": steps,
            "max_batch": svc.config.max_batch,
            "live_n": svc.tenants[TENANT].live_n,
            "counters": {k: svc.counters[k] - before[k] for k in before}}


def replay(state: State, record: dict) -> list:
    """The window's last microbatch again, launch by launch, through the
    search entry point the service calls: per launch its budget, query
    rows and each query's Theorem-3 union size."""
    from repro.core import search

    svc = state.service
    tenant = svc.tenants[TENANT]
    step = [s for s in record["steps"] if s["rows"]][-1]
    ys = state.queries[step["queries"]]
    q = ys.shape[0]
    bucket = next((b for b in svc.config.buckets if b >= q), q)
    ys = np.concatenate([ys, np.broadcast_to(ys[0], (bucket - q,
                                                      ys.shape[1]))])
    launches = []
    budget = search.default_budget(tenant.index, state.k)
    for final in [None] + step["budgets"]:
        if final is not None:
            if final == budget:
                continue
            budget = final
        with jax.profiler.TraceAnnotation("bench.replay"):
            res = search.knn_search_batch(tenant.index, ys, state.k, budget,
                                          block_rows=tenant.block_rows,
                                          validate=False)
            jax.block_until_ready(res)
        launches.append({"budget": int(budget), "q": q,
                         "num_candidates":
                             np.asarray(res.num_candidates)[:q].tolist()})
        if bool(np.asarray(res.exact).all()):
            break
    return launches


def release(state: State) -> None:
    state.service.tenants.clear()
    state.service = None
