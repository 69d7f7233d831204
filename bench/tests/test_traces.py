"""Trace reduction: busy time, kernel time, idle gaps and their labels."""

import jax
import jax.numpy as jnp
import pytest

from bench import traces

DEV = "/device:TPU:0"


def _events(device, host=()):
    return {"device": {DEV: [list(e) for e in device]},
            "host": [list(h) for h in host]}


def test_busy_is_the_union_of_overlapping_ops():
    ev = _events([(0, 10, "a.1"), (5, 10, "b"), (30, 10, "a.2")])
    r = traces.reduce(ev, (0, 50))
    assert r["busy_s"] == pytest.approx(25e-9)
    assert r["window_s"] == pytest.approx(50e-9)
    assert r["ops"] == pytest.approx({"a.1": 10e-9, "a.2": 10e-9,
                                      "b": 10e-9})
    assert traces.kernel_seconds(r["ops"], ("a",)) == pytest.approx(20e-9)


def test_ops_are_clipped_to_the_window():
    r = traces.reduce(_events([(0, 100, "k")]), (40, 60))
    assert r["busy_s"] == pytest.approx(20e-9)
    assert r["ops"]["k"] == pytest.approx(20e-9)


def test_idle_gaps_are_labelled_by_the_host_span_covering_them():
    ev = _events([(0, 10, "x"), (40, 10, "x"), (55, 5, "x")],
                 host=[(0, 100, "bench.window"), (10, 30, "bench.step"),
                       (50, 5, "bench.answer")])
    r = traces.reduce(ev, (0, 100))
    assert r["idle_gaps"] == [["bench.window", pytest.approx(40e-9)],
                              ["bench.step", pytest.approx(30e-9)],
                              ["bench.answer", pytest.approx(5e-9)]]


def test_busy_is_averaged_over_devices():
    ev = {"device": {DEV: [[0, 10, "x"]], "/device:TPU:1": [[0, 30, "x"]]},
          "host": []}
    assert traces.reduce(ev, (0, 40))["busy_s"] == pytest.approx(20e-9)


def test_no_device_events_reads_nothing():
    r = traces.reduce({"device": {}, "host": []})
    assert r["busy_s"] == 0.0 and r["ops"] == {}


def test_kernel_seconds_matches_names_exactly():
    ops = {"bregman_refine_batch.3": 2.0, "bregman_refine_batch_quant": 3.0}
    assert traces.kernel_seconds(ops, ("bregman_refine_batch",)) == 2.0
    assert traces.op_name("bregman_refine_batch_quant.12") == (
        "bregman_refine_batch_quant")


def test_instruction_name_from_hlo_text():
    text = ("%fusion.70 = f32[1000000,256]{1,0:T(8,128)} fusion(f32[1000000,"
            "256]{1,0:T(8,128)} %get-tuple-element.990), kind=kCustom")
    assert traces.instruction(text) == "fusion.70"
    assert traces.instruction("copy.3") == "copy.3"


def test_load_finds_the_benchmark_host_spans(tmp_path):
    f = jax.jit(lambda x: jnp.sin(x) @ x)
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with traces.capture(tmp_path / "t"):
        with jax.profiler.TraceAnnotation("bench.window"):
            f(x).block_until_ready()
    ev = traces.load(tmp_path / "t")
    assert [h[2] for h in ev["host"]] == ["bench.window"]
    assert traces.host_window(ev, "bench.window")[1] > 0


def test_recorded_chip_trace():
    """A trace of the fp32 cell on one v5e, cut down to its first 14 s
    (device operations of 0.2 ms or more, and every kernel event of the
    replayed microbatch)."""
    import gzip
    import json
    from pathlib import Path

    from bench import peaks
    from bench.spec import load_metric
    from bench.tests.cells import ROOT

    path = Path(__file__).parent / "data" / "trace_v5e_f32.json.gz"
    ev = json.load(gzip.open(path, "rt"))
    win = traces.reduce(ev["window"],
                        traces.host_window(ev["window"], "bench.window"))
    assert win["window_s"] == pytest.approx(14.0)
    assert win["busy_s"] == pytest.approx(13.983770559)
    assert [name for name, _ in win["device_ops"][:3]] == [
        "while.46", "cond.15", "while.44"]
    assert {g[0] for g in win["idle_gaps"]} <= {"bench.window", "bench.step",
                                                "bench.answer",
                                                "bench.submit"}
    rep = traces.reduce(ev["replay"])
    assert rep["busy_s"] == pytest.approx(9.306847006)
    reading = {
        "peaks": peaks.for_kind("TPU v5 lite"),
        "shape": {"n": 1_000_000, "d": 256, "m": 37, "storage": "f32"},
        "replay": {"ops": rep["ops"], "busy_s": rep["busy_s"], "launches": [
            {"q": 32, "budget": b, "num_candidates": [1_000_000] * 32}
            for b in (62_500, 1_000_000)]}}
    expect = {"kernel.refine_roofline": 1.4826, "device.microbatch_roofline":
              0.029806, "kernel.filter_prune_roofline": 2.7133}
    for name, value in expect.items():
        assert load_metric(ROOT, name).read(reading) == pytest.approx(
            value, rel=2e-3)
