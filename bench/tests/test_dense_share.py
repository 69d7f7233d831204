"""The refine.dense_share reader: the service's counter where it has one,
the program's path rule over the replayed launches of the knn_batch loop,
and nothing from a program without the counter or the rule."""

from bench import spec
from bench.tests.cells import ROOT


def _read(counters, replay=None, n=1_000_000):
    return spec.load_metric(ROOT, "refine.dense_share").read(
        {"window": {"counters": counters}, "replay": replay,
         "shape": {"n": n}})


def test_reads_the_service_counter():
    assert _read({"launches": 8, "dense_refines": 8}) == 100.0
    assert _read({"launches": 8, "dense_refines": 2}) == 25.0
    assert _read({"launches": 0, "dense_refines": 0}) is None


def test_reads_the_rule_over_replayed_knn_batch_launches():
    replay = {"launches": [{"q": 64, "budget": 62_500},
                           {"q": 64, "budget": 1_000_000}]}
    assert _read({"launches": 2}, replay) == 100.0
    replay = {"launches": [{"q": 64, "budget": 8_192},
                           {"q": 64, "budget": 1_000_000}]}
    assert _read({"launches": 2}, replay) == 50.0


def test_reads_nothing_without_counter_or_replay():
    assert _read({"launches": 8}) is None
    assert _read({"launches": 8}, {"launches": []}) is None
