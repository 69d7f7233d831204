"""The program's own phase scopes and service spans, read from a trace.

The search programs name their phases with ``jax.named_scope``
(``bp.filter``, ``bp.prune``, ``bp.refine`` with a nested ``gather``,
``bp.merge``), and the retrieval service opens host spans with
``jax.profiler.TraceAnnotation`` (``svc.step`` > ``svc.microbatch`` >
``svc.launch`` > ``svc.dispatch`` / ``svc.wait``, and ``svc.resolve``).

``load`` reads the newest ``.xplane.pb`` under a directory into plain
event lists, as ``traces.load`` does, but keeps each device operation's
phase and the service's spans with their arguments beside the
benchmark's.  ``reduce`` gives, inside a window, the device seconds of
each phase as the union of its operations' intervals (a ``while`` and the
fusions nested inside it count once), the shares of device busy time that
lie inside ``svc.launch`` spans and inside some phase, the host time of
each ``svc.microbatch`` span not covered by its ``svc.wait`` children, and
the device busy time, operations and idle gaps of ``traces.reduce``, the
gaps labelled by the innermost span of either kind.

Measure a cell's window with it (``--save`` keeps a cut-down copy of the
events, as the tests' recorded trace was made):

    python3 -m bench.phases --workload deep1m-ed-f32.svc32 --seed 7 \\
        --seconds 45
"""

from __future__ import annotations

import re
from pathlib import Path

from bench import traces

HOST_PREFIXES = ("bench.", "svc.")
# A phase is the first ``bp.<name>`` component of an operation's scope; a
# ``gather`` scope below it, before any nested ``jit(...)`` call, names a
# sub-phase (``bp.refine/gather``).  A scope has components below it; the
# last component is the primitive, and a ``gather`` primitive is no scope.
_PHASE = re.compile(r"(?:^|/)(bp\.\w+)((?:/[^/]+)*)")


def phase(op_name: str) -> str:
    """``bp.refine/gather`` for ``jit(f)/bp.refine/while/body/gather/...``,
    ``bp.filter`` for ``jit(f)/bp.filter/dot_general``, "" outside any."""
    m = _PHASE.search(op_name)
    if not m:
        return ""
    for part in m.group(2).split("/")[1:-1]:
        if part.startswith("jit("):
            break
        if part == "gather":
            return m.group(1) + "/gather"
    return m.group(1)


# A TPU trace names an operation by its HLO text without metadata,
# "%fusion.76 = s32[131072]{0:T(1024)S(1)} fusion(s32[32,4096]... ), ...",
# and its events carry no statistic that holds the scope.  The scope is in
# the op_name metadata of the executable's own HLO text, where the same
# instruction reads "%fusion.76 = s32[131072]{0:T(1024)S(1)} fusion(%x),
# ..., metadata={op_name="jit(f)/bp.prune/..."}": the two are joined on
# the instruction's name and result shape.
_OP = re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = (.+?) [a-z][\w-]*\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*op_name="([^"]*)"')


def op_key(text: str) -> tuple:
    """(instruction name, result shape) of a line of HLO text."""
    m = _OP.match(text)
    return (m.group(1), m.group(2)) if m else (traces.instruction(text), "")


def hlo_phases(texts) -> dict:
    """``{(instruction, result shape): phase}`` over the HLO texts of the
    executables a window launched; a key to which two programs give
    different phases maps to ""."""
    out: dict = {}
    for text in texts:
        for line in text.splitlines():
            m = _OP.match(line)
            if not m:
                continue
            name = _OP_NAME.search(line)
            ph = phase(name.group(1)) if name else ""
            key = (m.group(1), m.group(2))
            out[key] = ph if out.get(key, ph) == ph else ""
    return out


def load(directory: Path, hlo_texts=()) -> dict:
    """``{"device": {plane: [[start_ns, dur_ns, instruction, phase], ...]},
    "host": [[start_ns, dur_ns, name, {arg: value}], ...]}``, the phases
    read from ``hlo_texts``, the launched executables' HLO text."""
    from jax.profiler import ProfileData

    files = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    out = {"device": {}, "host": []}
    if not files:
        return out
    phases = hlo_phases(hlo_texts)
    for plane in ProfileData.from_file(str(files[-1])).planes:
        is_device = (plane.name.startswith("/device:")
                     and not plane.name.startswith("/device:CPU"))
        for line in plane.lines:
            if is_device and line.name == traces.OPS_LINE:
                out["device"].setdefault(plane.name, []).extend(
                    [float(e.start_ns), float(e.duration_ns),
                     traces.instruction(e.name),
                     phases.get(op_key(e.name), "")] for e in line.events)
            elif not is_device:
                out["host"] += [
                    [float(e.start_ns), float(e.duration_ns), e.name,
                     dict(e.stats)]
                    for e in line.events if e.name.startswith(HOST_PREFIXES)]
    return out


def _measure(intervals) -> float:
    return sum(b - a for a, b in traces._merge(intervals))


def _overlap(intervals, cover) -> float:
    """Length of the union of ``intervals`` that lies inside the union of
    ``cover``."""
    a_m, c_m = traces._merge(intervals), traces._merge(cover)
    total, j = 0.0, 0
    for a, b in a_m:
        while j < len(c_m) and c_m[j][1] <= a:
            j += 1
        k = j
        while k < len(c_m) and c_m[k][0] < b:
            total += min(b, c_m[k][1]) - max(a, c_m[k][0])
            k += 1
    return total


def reduce(events: dict, window=None, top: int = 10) -> dict:
    """``traces.reduce`` of the window, plus ``phases`` (device seconds per
    phase and sub-phase), ``in_launch_share`` and ``in_phase_share`` (of
    the device busy time) and ``microbatch_host_s`` (per
    ``svc.microbatch`` span, its time outside ``svc.wait`` spans)."""
    plain = {"device": {p: [e[:3] for e in evs]
                        for p, evs in events["device"].items()},
             "host": [h[:3] for h in events["host"]]}
    out = traces.reduce(plain, window, top)
    planes = [evs for evs in events["device"].values() if evs]
    if window is None and planes:
        window = (min(e[0] for evs in planes for e in evs),
                  max(e[0] + e[1] for evs in planes for e in evs))
    t0, t1 = window or (float("-inf"), float("inf"))

    def clip(spans):
        return [(max(s, t0), min(s + d, t1)) for s, d, *_ in spans
                if min(s + d, t1) > max(s, t0)]

    launches = clip(h for h in events["host"] if h[2] == "svc.launch")
    phases: dict = {}
    busy = in_launch = in_phase = 0.0
    for evs in planes:
        all_ops = clip(evs)
        scoped = clip(e for e in evs if e[3])
        busy += _measure(all_ops)
        in_launch += _overlap(all_ops, launches)
        in_phase += _measure(scoped)
        labels = {e[3] for e in evs if e[3]}
        for label in labels | {x.split("/")[0] for x in labels}:
            secs = _measure(clip(e for e in evs if e[3] == label
                                 or e[3].split("/")[0] == label))
            phases[label] = phases.get(label, 0.0) + secs * 1e-9 / len(planes)
    waits = [h for h in events["host"] if h[2] == "svc.wait"]
    host_s = []
    for s, d, name, _ in events["host"]:
        if name == "svc.microbatch" and t0 <= s and s + d <= t1:
            inside = [(max(ws, s), min(ws + wd, s + d)) for ws, wd, *_ in waits
                      if min(ws + wd, s + d) > max(ws, s)]
            host_s.append((d - _measure(inside)) * 1e-9)
    return dict(out, phases=phases,
                in_launch_share=in_launch / busy if busy else None,
                in_phase_share=in_phase / busy if busy else None,
                microbatch_host_s=host_s)


def window_launches(window: dict) -> list:
    """Every launch record of the window's microbatches, once each: the
    requests of one microbatch share its ``launches`` (``meta``)."""
    seen = {}
    for r in window["requests"]:
        if r.get("launches"):
            seen.setdefault(r["batch"], r["launches"])
    return [x for records in seen.values() for x in records]


def roofline_share(run: dict, work_phase: str, scopes) -> float | None:
    """Least seconds of ``work_phase`` (bench/work.py) over the window's
    launches, as a share (%) of the device seconds of the ``scopes``."""
    from bench import work

    ph = run.get("phases")
    launches = window_launches(run["window"])
    if not ph or not launches or not run["peaks"]:
        return None
    seconds = sum(ph["phases"].get(s, 0.0) for s in scopes)
    if seconds <= 0:
        return None
    least = sum(work.launch_seconds(x, run["shape"], run["peaks"])[work_phase]
                for x in launches)
    return 100.0 * least / seconds


METRICS = ("phase.filter_prune_roofline", "phase.refine_roofline",
           "service.queue_wait_ms", "service.host_ms_per_batch")


def measure(cell, seed: int, seconds: float, save: Path | None = None):
    """Set the cell up as ``bench/run.py`` does, trace one window, and
    read it through the program's scopes, spans and ``meta``."""
    import shutil
    import statistics

    import jax
    import numpy as np

    from bench import data, harness, peaks, spec

    devices = jax.devices()
    dev = devices[0]
    cfg, traffic = cell.config, cell.traffic
    if not cfg.get("cpu_ok") and (dev.platform != "tpu"
                                  or len(devices) < cell.chips):
        raise harness.NoAccelerator(f"{cell.name} needs {cell.chips} TPU "
                                    f"chip(s); JAX sees {dev.platform}")
    ds = cfg["dataset"]
    rows, queries = data.generate(seed, ds["n"], ds["d"],
                                  traffic["query_pool"], cfg["family"])
    index, _ = harness._build(np.asarray(rows), cfg)
    driver = spec.load_driver(cell.root, traffic["driver"])
    state = driver.setup(index, np.asarray(queries), cfg, traffic,
                         log=harness.say)
    # The driver keeps its tickets to itself; keep a reference to each so
    # the window's responses can be read after it closes.
    tickets, submit = {}, state.service.submit

    def keep(*args, **kwargs):
        ticket = submit(*args, **kwargs)
        tickets[ticket.uid] = ticket
        return ticket

    state.service.submit = keep
    trace_dir = cell.root / harness.TRACE_DIR / "phases"
    with traces.capture(trace_dir):
        rec = driver.window(state, seconds)
    for req in rec["requests"]:
        meta = tickets[req["uid"]].response.meta
        req.update(batch=meta.get("batch"), queue_s=meta.get("queue_s"),
                   launches=meta.get("launches"))
    events = load(trace_dir, _launched_hlo(state, window_launches(rec)))
    shutil.rmtree(trace_dir, ignore_errors=True)
    window = next(((s, s + d) for s, d, name, _ in events["host"]
                   if name == "bench.window"), None)
    red = reduce(events, window)
    reading = {"window": rec, "phases": red,
               "shape": {"n": index.n, "d": index.d, "m": index.m,
                         "storage": index.storage},
               "peaks": (peaks.for_kind(dev.device_kind)
                         if dev.platform == "tpu" else None)}
    queue = [r["queue_s"] for r in rec["requests"]
             if r.get("queue_s") is not None]
    out = {
        "device": {"platform": dev.platform, "kind": dev.device_kind},
        "metrics": {m: spec.load_metric(cell.root, m).read(reading)
                    for m in METRICS},
        # The end-to-end metrics of this traced window, beside a --trace 0
        # run's: what tracing costs.
        "traced": {m["name"]: spec.load_metric(cell.root, m["name"])
                   .read(reading) for m in cell.end_to_end
                   if m["name"] in ("qps", "p50_ms", "p95_ms")},
        "phases": red["phases"], "busy_s": red["busy_s"],
        "window_s": red["window_s"],
        "in_launch_share": red["in_launch_share"],
        "in_phase_share": red["in_phase_share"],
        "queue_wait_p50_ms": (1e3 * statistics.median(queue)
                              if queue else None),
        "span_host_ms_per_batch": (
            1e3 * statistics.mean(red["microbatch_host_s"])
            if red["microbatch_host_s"] else None),
        "launches": [{k: x[k] for k in ("tier", "budget", "q", "dispatch_s",
                                        "wait_s")}
                     | {"largest_union": max(x["num_candidates"])}
                     for x in window_launches(rec)],
        "device_ops": red["device_ops"], "idle_gaps": red["idle_gaps"]}
    if save is not None:
        _save(save, events, rec)
    driver.release(state)
    return out


def _launched_hlo(state, launches) -> list:
    """HLO text of the executables the window's launches ran: the
    single-device exact and approximate search programs of the service's
    one resident tenant, at each launch's budget and query bucket (the
    programs come back from JAX's caches, not compiled again)."""
    import numpy as np

    from repro.core import search

    svc = state.service
    tenant = next(iter(svc.tenants.values()))
    index = search._as_forest(tenant.index)
    texts = []
    for tier, budget, q in sorted({(x["tier"], x["budget"], x["q"])
                                   for x in launches}):
        bucket = next((b for b in svc.config.buckets if b >= q), q)
        ys = np.ones((bucket, index.d), np.float32)
        br = search.resolve_block_rows(tenant.block_rows, index.n, q=bucket,
                                       storage=index.storage)
        if tier == "approx":
            lowered = search._knn_search_batch_approx_jit.lower(
                index, ys, state.k, budget, np.float32(tenant.p_guarantee),
                br)
        else:
            lowered = search._knn_search_batch_jit.lower(
                index, ys, state.k, budget, br,
                search.resolve_env_block_rows(None))
        texts.append(lowered.compile().as_text())
    return texts


def _save(path: Path, events: dict, rec: dict, min_ns: float = 2e5) -> None:
    """The window's second microbatch: its device operations of at least
    ``min_ns`` and every kernel event, the host spans inside it, and its
    launch records."""
    import gzip
    import json

    mbs = sorted(h for h in events["host"] if h[2] == "svc.microbatch")
    s, d, _, args = mbs[1] if len(mbs) > 1 else mbs[0]
    t0, t1 = s, s + d
    cut = {"window": [t0, t1],
           "device": {p: [e for e in evs
                          if (e[1] >= min_ns or e[2].startswith("bregman_"))
                          and t0 <= e[0] and e[0] + e[1] <= t1]
                      for p, evs in events["device"].items()},
           "host": [h for h in events["host"]
                    if t0 <= h[0] and h[0] + h[1] <= t1],
           "launches": next(r["launches"] for r in rec["requests"]
                            if r.get("batch") == args.get("batch"))}
    with gzip.open(path, "wt") as f:
        json.dump(cut, f)


def main(argv=None) -> int:
    import argparse
    import json
    import os
    import sys

    from bench import harness, spec

    ap = argparse.ArgumentParser(
        description="Trace one window of a cell and read its phases.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--save", type=Path, default=None,
                    help="write one microbatch of the trace's events here")
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(harness.ROOT, args.workload)
    except spec.SpecError as e:
        harness.say(f"phases: {e}")
        return 2
    sys.path.insert(0, str(harness.ROOT / "src"))
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    jax.config.update("jax_compilation_cache_dir",
                      str(harness.ROOT / harness.CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        out = measure(cell, args.seed, args.seconds, args.save)
    except harness.NoAccelerator as e:
        harness.say(f"phases: {e}; nothing measured")
        return 1
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
