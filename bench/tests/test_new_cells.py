"""The Fonts configuration and the knn_batch cell: found by name, run
whole on the CPU at a tiny size, and correct against the reference."""

import copy
import json

import numpy as np
import pytest

from bench import harness, spec
from bench.tests.cells import ROOT, TINY, tiny_cell

FONTS = "fonts-isd-f32.svc32"
BATCH = "deep1m-ed-f32.batch64"
SERVICE_ONLY = {"service.rows_per_launch", "service.queue_wait_ms",
                "service.host_ms_per_batch"}


def _per_layer(cell: str) -> set:
    return {m["name"] for m in spec.load_cell(ROOT, cell).per_layer}


def test_new_cells_load_with_their_metrics():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    everything = {m["name"] for m in bench["per_layer"]}
    fonts = spec.load_cell(ROOT, FONTS)
    assert fonts.chips == 1
    assert fonts.config["family"] == "itakura_saito"
    assert fonts.config["dataset"] == {"name": "fonts", "n": 745_000,
                                       "d": 400}
    assert fonts.config["build"] == {"m": 50, "num_clusters": 1024}
    assert fonts.config["reduced"] == []
    assert fonts.traffic["driver"] == "service_closed_loop"
    assert _per_layer(FONTS) == everything - {"search.host_ms_per_call"}

    batch = spec.load_cell(ROOT, BATCH)
    assert batch.chips == 1
    assert batch.config["name"] == "deep1m-ed-f32"
    assert batch.traffic["driver"] == "batch_closed_loop"
    assert (batch.traffic["clients"], batch.traffic["rows_per_request"],
            batch.traffic["query_pool"]) == (1, 64, 4096)
    assert _per_layer(BATCH) == everything - SERVICE_ONLY
    for cell in (fonts, batch):
        assert {m["name"] for m in cell.end_to_end} == {
            m["name"] for m in bench["end_to_end"]}


def test_tiny_itakura_saito_cell_is_correct():
    """Fonts' family, width and partition count at n = 3,000, through the
    service, judged by the reference under limits of 1e-05."""
    cell = tiny_cell(family="itakura_saito",
                     dataset={"name": "tiny", "n": 3000, "d": 400},
                     build={"m": 50, "num_clusters": 16})
    result, _ = harness.run(cell, 2**35 + 16, 1.0, False)
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 32
    checks = result["checks"]
    assert checks["rank_gap"]["limit"] == checks["dist_err"]["limit"] == 1e-05
    assert checks["rank_gap"]["value"] <= 1e-05


def _batch_cell() -> spec.Cell:
    """The batch64 traffic and metrics on the tiny deployment."""
    real = spec.load_cell(ROOT, BATCH)
    cfg = spec.load_config(TINY)
    return spec.Cell(root=ROOT, name="tiny.batch64", chips=1, config=cfg,
                     traffic=copy.deepcopy(real.traffic),
                     end_to_end=real.end_to_end, per_layer=real.per_layer)


@pytest.mark.parametrize("trace", [False, True])
def test_batch_closed_loop_on_tiny_cell(trace):
    result, _ = harness.run(_batch_cell(), 2**34 + 9, 1.0, trace)
    line = json.loads(json.dumps(result))
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1
    metrics = line["metrics"]
    for m in metrics.values():
        assert m["value"] > 0
    if trace:
        assert {"search.launches_per_batch", "prune.budget_share",
                "search.host_ms_per_call"} <= set(metrics)
        assert not SERVICE_ONLY & set(metrics)
    else:
        assert {"qps", "p50_ms", "p95_ms", "setup_s"} <= set(metrics)


def test_batch_closed_loop_record():
    """The window record has the keys the service driver's readers use,
    one step per call of 64 rows."""
    cell = _batch_cell()
    driver = spec.load_driver(ROOT, "batch_closed_loop")
    from bench import data
    from repro.core.index import build_index

    cfg = cell.config
    rows, queries = data.generate(5, 3000, 32, 4096, cfg["family"])
    rows, queries = np.asarray(rows), np.asarray(queries)
    index = build_index(rows, cfg["family"], **cfg["build"])
    state = driver.setup(index, queries, cfg, cell.traffic,
                         log=lambda _: None)
    rec = driver.window(state, 0.5)
    assert set(rec) >= {"t0", "close", "last", "requests", "steps",
                        "max_batch", "live_n", "counters"}
    assert rec["max_batch"] == 64 and rec["live_n"] == 3000
    assert len(rec["requests"]) == len(rec["steps"]) >= 1
    for req, step in zip(rec["requests"], rec["steps"]):
        assert len(req["queries"]) == step["rows"] == 64
        assert req["quality"] == "exact"
        assert req["ids"].shape == req["dists"].shape == (64, cfg["k"])
        assert step["queries"] == req["queries"]
        assert step["launches"] >= 1 and len(step["budgets"]) == 1
        assert 0 <= step["wait_s"] <= step["wall_s"]
        assert max(step["queries"]) < 4096 - 64   # warm-up rows held out
    assert rec["counters"]["launches"] == sum(s["launches"]
                                              for s in rec["steps"])
    launches = driver.replay(state, rec)
    assert launches and launches[-1]["budget"] == rec["steps"][-1][
        "budgets"][0]
    assert all(len(x["num_candidates"]) == 64 for x in launches)
    driver.release(state)
