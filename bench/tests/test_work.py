"""Least-work counts: what a phase must do, whatever implements it."""

import pytest

from bench import peaks, work

V5E = peaks.for_kind("TPU v5 lite")
DEEP = {"n": 1_000_000, "d": 256, "m": 37}


def test_refine_reads_each_candidate_row_once():
    launch = {"q": 32, "budget": 1_000_000, "num_candidates": [1_000_000] * 32}
    one = dict(launch, q=1, num_candidates=[1_000_000])
    f32 = dict(DEEP, storage="f32")
    # 1 GB read once for 32 queries as for 1: the least time is that read.
    for lt in (launch, one):
        assert work.launch_seconds(lt, f32, V5E)["refine"] == pytest.approx(
            1_000_000 * 256 * 4 / 819e9)


def test_int8_counts_codes_and_decode_scalars():
    launch = {"q": 32, "budget": 1_000_000, "num_candidates": [1_000_000] * 32}
    s = work.launch_seconds(launch, dict(DEEP, storage="int8"), V5E)
    assert s["refine"] == pytest.approx(1_000_000 * (256 + 8) / 819e9)
    fp = 2 * 1_000_000 * 2 * (37 + 8) / 819e9
    assert s["filter_prune"] == pytest.approx(fp)


def test_overflowed_launch_counts_its_budget_only():
    launch = {"q": 2, "budget": 62_500, "num_candidates": [1_000_000, 10]}
    ops, nbytes = work.refine(256, "f32", 62_500, 62_510)
    s = work.launch_seconds(launch, dict(DEEP, storage="f32"), V5E)
    assert s["refine"] == work.least_seconds(ops, nbytes, V5E, "f32")


def test_unknown_device_is_an_error():
    with pytest.raises(peaks.UnknownDevice):
        peaks.for_kind("TPU v99")


def test_kernel_and_microbatch_shares_never_pass_100_percent():
    from bench.spec import load_metric
    from bench.tests.cells import ROOT

    launch = {"q": 32, "budget": 1_000_000, "num_candidates": [1_000_000] * 32}
    shape = dict(DEEP, storage="f32")
    least = work.launch_seconds(launch, shape, V5E)
    # Device times at the least times themselves: every share reads 100%.
    run = {"peaks": V5E, "shape": shape, "replay": {
        "launches": [launch], "busy_s": sum(least.values()),
        "ops": {"bregman_refine_batch.3": least["refine"],
                "bregman_ub_matrix.1": least["filter_prune"] / 2,
                "bregman_filter_prune.4": least["filter_prune"] / 2}}}
    for name in ("kernel.refine_roofline", "kernel.filter_prune_roofline",
                 "device.microbatch_roofline"):
        assert load_metric(ROOT, name).read(run) == pytest.approx(100.0)
    assert load_metric(ROOT, "device.microbatch_roofline").read(
        dict(run, replay=None)) is None
