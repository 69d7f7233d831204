"""The comparison that decides ``correct``.

Every sampled answer of the window is held against the reference's exact
top-k over the same point set:

* ``missing``: answers that never came, or came shed (limit 0);
* ``not_exact``: answers labelled anything but ``exact`` (limit 0);
* ``bad_ids``: answers whose ids are out of range or repeated (limit 0);
* ``rank_gap``: the widest gap, over answers and ranks j, between the
  reference's D_f of the answer's j-th nearest row and the reference's
  own j-th distance, over the query's term scale.  A wrong neighbour
  makes it positive; the same set of rows gives exactly 0;
* ``dist_err``: the widest gap between a distance the answer reports and
  the reference's D_f of that row, over the query's term scale.

The term scale, sum |f(y)| + |y f'(y)|, is the size of the terms whose
cancellation limits any float32 evaluation of D_f near the query.
"""

from __future__ import annotations

import numpy as np

# Compared with these limits of 0; the configuration gives the others.
COUNT_LIMITS = {"missing": 0, "not_exact": 0, "bad_ids": 0}


def compare(answers: list, queries: np.ndarray, ref, k: int,
            limits: dict, seed: int, cap: int) -> dict:
    """``answers``: dicts with ``query`` (a row of ``queries``), ``ids``,
    ``dists`` and ``quality`` (None where the answer never came).  All
    are counted; ``cap`` of those that came, drawn from ``seed``, are
    judged against the reference.

    Returns ``{name: {"value": v, "limit": l}}`` and nothing else.
    """
    came = [a for a in answers
            if a["quality"] is not None and a["quality"] != "shed"]
    missing = len(answers) - len(came)
    not_exact = sum(a["quality"] != "exact" for a in came)
    ok = []
    bad_ids = 0
    for i in sample(len(came), seed, cap):
        a = came[i]
        ids = np.asarray(a["ids"]).reshape(-1)
        if (ids.shape[0] != k or len(set(ids.tolist())) != k
                or ids.min() < 0 or ids.max() >= ref.points.shape[0]):
            bad_ids += 1
        else:
            ok.append(a)
    rank_gap = dist_err = 0.0
    if ok:
        qi = np.asarray([a["query"] for a in ok])
        ys = queries[qi]
        ids = np.stack([np.asarray(a["ids"]).reshape(-1) for a in ok])
        got = np.stack([np.asarray(a["dists"], np.float64).reshape(-1)
                        for a in ok])
        # Padded to ``cap`` rows, so that every run reuses one program.
        pad = max(cap - len(ok), 0)
        ref_d, own = ref.judge(np.concatenate([ys, ys[:1].repeat(pad, 0)]),
                               np.concatenate([ids, ids[:1].repeat(pad, 0)]),
                               k)
        ref_d, own = ref_d[:len(ok)], own[:len(ok)]
        s = ref.term_scale(ys).astype(np.float64)[:, None]
        own = own.astype(np.float64)
        gap = (np.sort(own, axis=1) - ref_d.astype(np.float64)) / s
        err = np.abs(got - own) / s
        rank_gap = float(np.max(gap))
        dist_err = float(np.max(np.where(np.isfinite(err), err, np.inf)))
    out = {"missing": missing, "not_exact": not_exact, "bad_ids": bad_ids,
           "rank_gap": rank_gap, "dist_err": dist_err}
    lims = dict(COUNT_LIMITS)
    lims.update({"rank_gap": limits["rank_gap"],
                 "dist_err": limits["dist_err"]})
    return {name: {"value": out[name], "limit": lims[name]}
            for name in out}


def passed(checks: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in checks.values())


def sample(n_answers: int, seed: int, cap: int) -> np.ndarray:
    """Which answers to compare: all of them up to ``cap``, else ``cap``
    drawn from the seed."""
    if n_answers <= cap:
        return np.arange(n_answers)
    rng = np.random.default_rng(int(seed))
    return np.sort(rng.choice(n_answers, cap, replace=False))
