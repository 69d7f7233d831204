"""The comparison refuses the control and every fault the svc32 cell can
have, planted under a whole run of the tiny cell on the CPU."""

import numpy as np
import pytest

from bench import control, harness
from bench.tests.cells import tiny_cell


@pytest.mark.parametrize("storage,low", [("f32", "bf16"), ("int8", "int4")])
def test_control_comes_out_not_correct(storage, low):
    cell = tiny_cell(storage=storage, control=low)
    for seed in (11, 2**36 + 1, 13):
        checks = control.control_checks(cell, seed, 160)
        assert checks["rank_gap"]["value"] > checks["rank_gap"]["limit"] or (
            checks["dist_err"]["value"] > checks["dist_err"]["limit"])


def _altered(res, prev):
    # The last neighbour of every answer is replaced by another row.
    ids = np.asarray(res.ids).copy()
    ids[:, -1] = (ids[:, -1] + 1) % 3000
    return res._replace(ids=ids)


def _half_left_out(res, prev):
    # Only the first half of the microbatch was searched; the second half
    # is handed the first half's answers.
    ids, dists = np.asarray(res.ids).copy(), np.asarray(res.dists).copy()
    h = ids.shape[0] // 2
    ids[h:], dists[h:] = ids[:ids.shape[0] - h], dists[:ids.shape[0] - h]
    return res._replace(ids=ids, dists=dists)


def _unchanged(res, prev):
    # The launch hands back the previous launch's result unchanged.
    return res if prev is None else prev


FAULTS = {"answer_altered": _altered, "half_batch_left_out": _half_left_out,
          "state_unchanged": _unchanged}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_fault_in_timed_path_is_not_correct(fault, monkeypatch):
    from repro.core import search

    real = search.knn_search_batch
    last = [None]

    def broken(*args, **kwargs):
        res = real(*args, **kwargs)
        out = FAULTS[fault](res, last[0])
        last[0] = res
        return out

    monkeypatch.setattr(search, "knn_search_batch", broken)
    result, _ = harness.run(tiny_cell(), 21, 1.0, False)
    assert result["correct"] is False


def test_answer_that_never_comes_is_not_correct(monkeypatch):
    from repro.serve import retrieval

    real = retrieval.RetrievalService._resolve

    def drop_some(self, req, *args, **kwargs):
        real(self, req, *args, **kwargs)
        if req.uid % 7 == 3:
            req.ticket.done = False

    monkeypatch.setattr(retrieval.RetrievalService, "_resolve", drop_some)
    result, _ = harness.run(tiny_cell(), 22, 1.0, False)
    assert result["correct"] is False
    assert result["checks"]["missing"]["value"] > 0
    assert result["failed"] > 0
