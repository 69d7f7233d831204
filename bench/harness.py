"""One run of one cell: set-up, the measured window, the check, the result.

``run`` returns the result object and the check's lines; ``main`` is the
command line (``bench/run.py``).  Set-up is everything from the start of
the process until the window opens: importing JAX, making the data on the
device, ``build_index``, and the traffic driver's own set-up (for the
service: register, warm its bucket, one full round of traffic).
"""

from __future__ import annotations

import argparse
import collections
import gc
import gzip
import json
import logging
import os
import shutil
import sys
import time
from pathlib import Path

import numpy as np

from bench import check, spec

ROOT = Path(__file__).resolve().parents[1]
TRACE_DIR = ".bench_trace"
CACHE_DIR = ".jax_cache"
# Answers held against the reference in one run, drawn from the seed
# where the window returned more.
JUDGE_CAP = 256


class NoAccelerator(RuntimeError):
    pass


def say(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class _Compiles:
    """Executables built or loaded from the cache since ``start``."""

    def __init__(self):
        import jax

        self.events = collections.Counter()
        self.on = False
        jax.monitoring.register_event_duration_secs_listener(self._seen)

    def _seen(self, event: str, _secs: float, **_kw) -> None:
        if self.on and event == "/jax/core/compile/backend_compile_duration":
            self.events["compiles"] += 1


class _BuildLog(logging.Handler):
    def __init__(self):
        super().__init__(logging.INFO)
        self.seconds = None

    def emit(self, record):
        if hasattr(record, "build_seconds"):
            self.seconds = record.build_seconds


def _build(rows, config: dict):
    """``build_index`` with the configuration's arguments; its own seed
    stays at its default, as a deployment's build would."""
    from repro.core.index import build_index

    log = _BuildLog()
    logger = logging.getLogger("repro.core.index")
    logger.addHandler(log)
    logger.setLevel(logging.INFO)
    try:
        index = build_index(rows, config["family"],
                            quantize=config["storage"] == "int8",
                            **config["build"])
    finally:
        logger.removeHandler(log)
    return index, log.seconds


def _answers(requests: list) -> list:
    """One answer per query row of every request of the window."""
    out = []
    for r in requests:
        for j, qi in enumerate(r["queries"]):
            came = r["quality"] is not None
            out.append({"query": qi, "quality": r["quality"],
                        "ids": r["ids"][j] if came else None,
                        "dists": r["dists"][j] if came else None})
    return out


def _read_traces(driver, state, rec: dict, trace_dir: Path,
                 save_events: Path | None):
    """The traced window reduced, and a replay of its last microbatch in a
    profiler session of its own: the launches and their device time."""
    from bench import traces

    window_trace = traces.load(trace_dir / "window")
    window = None
    if window_trace["device"]:
        window = traces.reduce(
            window_trace, traces.host_window(window_trace, "bench.window"))
    with traces.capture(trace_dir / "replay"):
        launches = driver.replay(state, rec)
    replay_trace = traces.load(trace_dir / "replay")
    reduced = traces.reduce(replay_trace)
    replay = {"launches": launches, "ops": reduced["ops"],
              "busy_s": reduced["busy_s"]}
    top = sorted(reduced["ops"].items(), key=lambda kv: -kv[1])[:12]
    say(f"replay: launches (budget, largest union) "
        f"{[(x['budget'], max(x['num_candidates'])) for x in launches]}; "
        f"device seconds {top}")
    if save_events is not None:
        with gzip.open(save_events, "wt") as f:
            json.dump({"window": window_trace, "replay": replay_trace}, f)
    shutil.rmtree(trace_dir, ignore_errors=True)
    return window, replay


def run(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
        t_start: float | None = None, save_events: Path | None = None):
    """(result dict, check lines).  Raises NoAccelerator off the chip
    unless the cell's config allows the CPU (tests only)."""
    import jax

    from bench import data, peaks, reference, traces

    t_start = time.perf_counter() if t_start is None else t_start
    devices = jax.devices()
    dev = devices[0]
    cfg, traffic = cell.config, cell.traffic
    if not cfg.get("cpu_ok") and (dev.platform != "tpu"
                                  or len(devices) < cell.chips):
        raise NoAccelerator(f"{cell.name} needs {cell.chips} TPU chip(s); "
                            f"JAX sees {len(devices)} {dev.platform} "
                            "device(s)")
    peak = peaks.for_kind(dev.device_kind) if dev.platform == "tpu" else None
    compiles = _Compiles()

    t0 = time.perf_counter()
    ds = cfg["dataset"]
    rows, queries = data.generate(seed, ds["n"], ds["d"],
                                  traffic["query_pool"], cfg["family"])
    rows, queries = np.asarray(rows), np.asarray(queries)
    t1 = time.perf_counter()
    index, build_s = _build(rows, cfg)
    t2 = time.perf_counter()
    say(f"setup: data {ds['n']} x {ds['d']} + {len(queries)} queries made "
        f"on the device in {t1 - t0} s; build_index {t2 - t1} s "
        f"(M={index.m}, storage {index.storage}; {build_s})")
    driver = spec.load_driver(cell.root, traffic["driver"])
    state = driver.setup(index, queries, cfg, traffic, log=say)
    setup_s = time.perf_counter() - t_start
    say(f"setup_s {setup_s}")

    compiles.on = True
    if trace:
        with traces.capture(cell.root / TRACE_DIR / "window"):
            rec = driver.window(state, seconds)
    else:
        rec = driver.window(state, seconds)
    compiles.on = False
    say(f"compiles in window: {compiles.events['compiles']}")
    stats = dev.memory_stats() or {}
    memory_peak = stats.get("peak_bytes_in_use")

    window = replay = None
    if trace:
        window, replay = _read_traces(driver, state, rec,
                                      cell.root / TRACE_DIR, save_events)
    shape = {"n": index.n, "d": index.d, "m": index.m,
             "storage": index.storage}
    driver.release(state)
    del index, state
    gc.collect()

    t3 = time.perf_counter()
    answers = _answers(rec["requests"])
    ref = reference.Reference(rows, cfg["storage"], cfg["family"])
    checks = check.compare(answers, queries, ref, int(cfg["k"]),
                           cfg["limits"], seed, JUDGE_CAP)
    del ref
    say(f"reference: {min(len(answers), JUDGE_CAP)} of {len(answers)} "
        f"answers judged in {time.perf_counter() - t3} s")

    reading = {"setup_s": setup_s, "memory_peak_bytes": memory_peak,
               "window": rec, "shape": shape, "peaks": peak,
               "trace": window, "replay": replay}
    wanted = cell.per_layer if trace else cell.end_to_end
    metrics = {}
    for m in wanted:
        value = spec.load_metric(cell.root, m["name"]).read(reading)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    failed = sum(r["quality"] != "exact" for r in rec["requests"])
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": check.passed(checks),
              "attempted": len(rec["requests"]), "failed": failed,
              "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = window["busy_s"] if window else 0.0
        device["window_s"] = window["window_s"] if window else 0.0
        if window:
            result["breakdown"] = {"device_ops": window["device_ops"],
                                   "idle_gaps": window["idle_gaps"]}
    result["checks"] = checks
    lines = [f"check {name} {c['value']} limit {c['limit']}"
             for name, c in checks.items()]
    return result, lines


def main(argv=None) -> int:
    t_start = time.perf_counter()
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--save-events", type=Path, default=None,
                    help="with --trace 1, write the trace's event lists "
                         "to this JSON file")
    args = ap.parse_args(argv)
    try:
        cell = spec.load_cell(ROOT, args.workload)
    except spec.SpecError as e:
        say(f"bench: {e}")
        return 2
    if not (ROOT / "src" / "repro").is_dir():
        say(f"bench: the program is not here ({ROOT / 'src' / 'repro'})")
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    # libtpu otherwise writes its logs to a fixed path under /tmp.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax

    from bench import peaks

    jax.config.update("jax_compilation_cache_dir", str(ROOT / CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        result, lines = run(cell, args.seed, args.seconds, bool(args.trace),
                            t_start=t_start, save_events=args.save_events)
    except (NoAccelerator, peaks.UnknownDevice) as e:
        say(f"bench: {e}; nothing measured")
        return 1
    for line in lines:
        say(line)
    print(json.dumps(result), flush=True)
    return 0
