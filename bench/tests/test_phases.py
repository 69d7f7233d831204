"""Phase scopes and service spans read from a trace (bench/phases.py),
and the readers of the metrics they feed."""

import gzip
import json
from pathlib import Path

import pytest

from bench import harness, phases, spec, traces
from bench.tests.cells import ROOT, tiny_cell

DEV = "/device:TPU:0"
DATA = Path(__file__).parent / "data"


@pytest.mark.parametrize("op_name, expect", [
    ("jit(_knn_search_batch_jit)/bp.filter/dot_general", "bp.filter"),
    ("jit(f)/bp.prune/while/body/cond", "bp.prune"),
    ("jit(f)/bp.refine/gather/jit(_take)/gather", "bp.refine/gather"),
    ("jit(f)/bp.refine/while/body/gather/jit(_take)/gather",
     "bp.refine/gather"),
    ("jit(f)/bp.refine/jit(_take)/gather", "bp.refine"),
    ("jit(f)/bp.refine/gather", "bp.refine"),
    ("jit(f)/bp.prune/while/body/vmap(jit(searchsorted))/while/body/gather",
     "bp.prune"),
    ("jit(f)/bp.merge/all_gather", "bp.merge"),
    ("jit(f)/reduce_sum", ""),
    ("", ""),
])
def test_phase_of_an_operation_scope(op_name, expect):
    assert phases.phase(op_name) == expect


# An operation as a v5e trace names it, and the same instruction in the
# executable's HLO text (cut from a chip run of the search program).
TRACE_OP = ("%while.44 = (s32[]{:T(128)}, s32[32,1024]{1,0:T(8,128)S(1)}) "
            "while((s32[]{:T(128)}, s32[32,1024]{1,0:T(8,128)S(1)}) "
            "%tuple.149), condition=%wide.region_21.45.clone.clone, "
            "body=%wide.region_20.44.clone.sunk.clone")
HLO_LINE = ("  %while.44 = (s32[]{:T(128)}, s32[32,1024]{1,0:T(8,128)S(1)}) "
            "while(%tuple.149), condition=%wide.region_21.45.clone.clone, "
            "body=%wide.region_20.44.clone.sunk.clone, metadata={op_name="
            '"jit(_knn_search_batch_jit)/bp.prune/while/body/closed_call/'
            'cond/branch_1_fun/vmap(jit(searchsorted))/vmap()/while" '
            "stack_frame_id=131}")


def test_trace_operations_join_the_hlo_text_by_name_and_shape():
    key = phases.op_key(TRACE_OP)
    assert key == ("while.44", "(s32[]{:T(128)}, "
                               "s32[32,1024]{1,0:T(8,128)S(1)})")
    fusion = "  ROOT %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop"
    texts = [HLO_LINE + "\n" + fusion + ', metadata={op_name="j/bp.filter/x"}',
             fusion + ', metadata={op_name="j/bp.refine/gather/y"}']
    got = phases.hlo_phases(texts)
    assert got[key] == "bp.prune"
    assert got[("fusion.7", "f32[8]{0}")] == ""     # two programs disagree
    assert phases.op_key("copy.3") == ("copy.3", "")


def _events(device, host=()):
    return {"device": {DEV: [list(e) for e in device]},
            "host": [list(h) for h in host]}


def test_nested_operations_count_once_per_phase():
    """A refine loop and the two gather fusions inside it are 100 ns of
    refine, not 170; an unscoped copy counts in busy time only."""
    ev = _events([(0, 100, "while.4", "bp.refine"),
                  (10, 30, "fusion.1", "bp.refine/gather"),
                  (50, 40, "fusion.2", "bp.refine/gather"),
                  (100, 20, "fusion.7", "bp.filter"),
                  (120, 5, "copy.1", "")],
                 host=[(0, 110, "svc.launch", {"batch": 0})])
    r = phases.reduce(ev, (0, 130))
    assert r["phases"] == pytest.approx({"bp.refine": 100e-9,
                                         "bp.refine/gather": 70e-9,
                                         "bp.filter": 20e-9})
    assert r["busy_s"] == pytest.approx(125e-9)
    assert r["in_phase_share"] == pytest.approx(120 / 125)
    assert r["in_launch_share"] == pytest.approx(110 / 125)
    # traces.reduce's per-instruction sums still count each event.
    assert sum(r["ops"].values()) == pytest.approx(195e-9)


def test_microbatch_host_time_leaves_out_its_waits():
    ev = _events([(20, 60, "fusion.1", "bp.refine")],
                 host=[(0, 100, "svc.microbatch", {"batch": 3}),
                       (10, 80, "svc.launch", {"batch": 3}),
                       (15, 70, "svc.wait", {}),
                       (200, 10, "svc.microbatch", {"batch": 4})])
    r = phases.reduce(ev, (0, 300))
    assert r["microbatch_host_s"] == pytest.approx([30e-9, 10e-9])


def test_idle_gaps_are_named_by_the_innermost_service_span():
    """Of the spans that cover most of a gap, the shortest names it."""
    ev = _events([(0, 10, "x", "bp.filter"), (40, 10, "x", "bp.refine"),
                  (70, 30, "x", "bp.refine")],
                 host=[(0, 100, "bench.window", {}),
                       (0, 100, "bench.step", {}),
                       (0, 100, "svc.step", {}),
                       (5, 70, "svc.microbatch", {"batch": 1}),
                       (8, 34, "svc.resolve", {"batch": 1})])
    r = phases.reduce(ev, (0, 100))
    assert r["idle_gaps"] == [["svc.resolve", pytest.approx(30e-9)],
                              ["svc.microbatch", pytest.approx(20e-9)]]


def test_the_recorded_trace_without_scopes_reads_as_before():
    """The older recording, read with no phases and no span arguments,
    gives every number traces.reduce gave it."""
    ev = json.load(gzip.open(DATA / "trace_v5e_f32.json.gz", "rt"))
    for part in ("window", "replay"):
        old = ev[part]
        new = {"device": {p: [e + [""] for e in evs]
                          for p, evs in old["device"].items()},
               "host": [h + [{}] for h in old["host"]]}
        window = (traces.host_window(old, "bench.window")
                  if part == "window" else None)
        before = traces.reduce(old, window)
        after = phases.reduce(new, window)
        assert {k: after[k] for k in before} == before
        assert after["phases"] == {} and after["in_phase_share"] == 0.0


def test_launch_records_are_counted_once_per_microbatch():
    shared = [{"budget": 10}, {"budget": 20}]
    window = {"requests": [{"batch": 0, "launches": shared},
                           {"batch": 0, "launches": shared},
                           {"batch": 1, "launches": [{"budget": 30}]},
                           {"batch": None, "launches": None}]}
    assert [x["budget"] for x in phases.window_launches(window)] == [
        10, 20, 30]


def test_readers_find_nothing_in_a_run_without_the_programs_telemetry():
    """A program without scopes, spans or the new counters (the parent of
    the change that added them): every new reader returns None."""
    run = {"window": {"requests": [{"queries": [0], "budget": 10}],
                      "counters": {"launches": 2}},
           "trace": None, "replay": None, "peaks": {"x": 1},
           "shape": {"n": 10, "d": 2, "m": 1, "storage": "f32"}}
    for name in phases.METRICS:
        assert spec.load_metric(ROOT, name).read(run) is None


def test_service_readers_divide_the_window_counters():
    run = {"window": {"counters": {"microbatches": 4, "host_s": 0.02,
                                   "microbatch_requests": 8,
                                   "queue_s": 0.004}}}
    read = {m: spec.load_metric(ROOT, m).read(run)
            for m in ("service.queue_wait_ms", "service.host_ms_per_batch")}
    assert read == pytest.approx({"service.queue_wait_ms": 0.5,
                                  "service.host_ms_per_batch": 5.0})


def test_tiny_cell_reports_the_service_metrics():
    result, _ = harness.run(tiny_cell(), 2**33 + 9, 1.5, True)
    for name in ("service.queue_wait_ms", "service.host_ms_per_batch"):
        assert result["metrics"][name]["unit"] == "ms"
        assert result["metrics"][name]["value"] > 0


def test_measure_reads_meta_and_spans_of_the_tiny_cell():
    """The measuring command on the CPU: no device planes, so no phases,
    but the service's meta and spans of the window."""
    out = phases.measure(tiny_cell(), 2**33 + 11, 1.5)
    assert out["device"]["platform"] == "cpu"
    assert out["phases"] == {} and out["busy_s"] == 0.0
    assert out["metrics"]["service.queue_wait_ms"] > 0
    assert out["queue_wait_p50_ms"] > 0
    assert out["span_host_ms_per_batch"] > 0
    assert out["launches"] and all(x["q"] == 32 for x in out["launches"])
    assert set(out["traced"]) == {"qps", "p50_ms", "p95_ms"}
    assert out["traced"]["qps"] > 0


def test_recorded_chip_trace_with_scopes():
    """One microbatch of the fp32 cell on one v5e (a budget-62,500 launch,
    then a budget-1,000,000 one), traced with the program's scopes and
    spans: device operations of 0.2 ms or more and every kernel event,
    each with the phase bench/phases.py joined to it, the service's spans
    and the microbatch's launch records."""
    from bench import peaks

    ev = json.load(gzip.open(DATA / "trace_v5e_f32_phases.json.gz", "rt"))
    r = phases.reduce(ev, ev["window"])
    assert r["window_s"] == pytest.approx(9.316684739)
    assert r["phases"] == pytest.approx({
        "bp.filter": 0.021022591, "bp.prune": 8.73907733,
        "bp.refine": 0.53868126, "bp.refine/gather": 0.310924421})
    # The prune's per-block loops and the fusions nested in them count
    # once: their events sum to four times the phase.
    evs = ev["device"][DEV]
    nested = sum(e[1] for e in evs if e[3] == "bp.prune") * 1e-9
    assert nested > 3.9 * r["phases"]["bp.prune"]
    assert sum(r["phases"][p] for p in ("bp.filter", "bp.prune",
                                        "bp.refine")) <= r["busy_s"]
    assert r["in_launch_share"] > 0.9999 and r["in_phase_share"] > 0.999
    assert r["microbatch_host_s"] == pytest.approx([0.006778189])
    reading = {
        "window": {"requests": [{"batch": 2, "launches": ev["launches"]}] * 2},
        "phases": r, "peaks": peaks.for_kind("TPU v5 lite"),
        "shape": {"n": 1_000_000, "d": 256, "m": 37, "storage": "f32"}}
    reading["replay"] = {"ops": r["ops"], "busy_s": r["busy_s"],
                         "launches": ev["launches"]}
    read = {m: spec.load_metric(ROOT, m).read(reading) for m in (
        "phase.filter_prune_roofline", "phase.refine_roofline",
        "kernel.filter_prune_roofline", "kernel.refine_roofline")}
    assert read == pytest.approx({
        "phase.filter_prune_roofline": 0.016502842,
        "phase.refine_roofline": 0.246611387,
        "kernel.filter_prune_roofline": 2.7133747,
        "kernel.refine_roofline": 1.4825943}, rel=1e-6)
    # A phase holds its kernels and more: its share is the lower.
    assert (read["phase.filter_prune_roofline"]
            <= read["kernel.filter_prune_roofline"])
    assert read["phase.refine_roofline"] <= read["kernel.refine_roofline"]
