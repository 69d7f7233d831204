"""Reduce a profiler trace to device busy time, kernel time and gaps.

``load`` reads the newest ``.xplane.pb`` under a directory into plain
event lists: per device, the operations of its ``XLA Ops`` line; on the
host, the spans the benchmark itself opened (names starting ``bench.``).
``reduce`` turns those lists into the numbers the metric readers use.
Both lists are plain JSON, so a trace cut down from a chip run can be
kept with the tests.
"""

from __future__ import annotations

import contextlib
import re
import shutil
from pathlib import Path

HOST_PREFIX = "bench."
OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"(\.\d+)+$")
# A TPU trace names an operation by its HLO text, "%fusion.70 = f32[...]
# fusion(...)"; the instruction's name is what precedes " = ".
_INSTRUCTION = re.compile(r"^%?([^\s=]+)\s*=")


def instruction(name: str) -> str:
    m = _INSTRUCTION.match(name)
    return m.group(1) if m else name


@contextlib.contextmanager
def capture(directory: Path):
    """Profile the body into ``directory`` (emptied first)."""
    import jax

    directory = Path(directory)
    shutil.rmtree(directory, ignore_errors=True)
    directory.mkdir(parents=True)
    jax.profiler.start_trace(str(directory))
    try:
        yield directory
    finally:
        jax.profiler.stop_trace()


def load(directory: Path) -> dict:
    """``{"device": {plane: [[start_ns, dur_ns, name], ...]},
    "host": [[start_ns, dur_ns, name], ...]}``."""
    from jax.profiler import ProfileData

    files = sorted(Path(directory).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not files:
        return {"device": {}, "host": []}
    data = ProfileData.from_file(str(files[-1]))
    device, host = {}, []
    for plane in data.planes:
        is_device = (plane.name.startswith("/device:")
                     and not plane.name.startswith("/device:CPU"))
        for line in plane.lines:
            if is_device and line.name == OPS_LINE:
                device.setdefault(plane.name, []).extend(
                    [float(e.start_ns), float(e.duration_ns),
                     instruction(e.name)] for e in line.events)
            elif not is_device:
                host.extend([float(e.start_ns), float(e.duration_ns), e.name]
                            for e in line.events
                            if e.name.startswith(HOST_PREFIX))
    return {"device": device, "host": host}


def op_name(name: str) -> str:
    """An operation's name without XLA's numeric suffixes: a kernel's own
    name."""
    return _SUFFIX.sub("", name)


def _merge(intervals):
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def host_window(events: dict, span: str):
    """(start_ns, end_ns) of the first host span named ``span``."""
    for start, dur, name in events["host"]:
        if name == span:
            return start, start + dur
    return None


def reduce(events: dict, window=None, top: int = 10) -> dict:
    """Device busy time, per-instruction time and idle gaps in a window.

    ``window`` is ``(start_ns, end_ns)``; by default the extent of the
    device operations.  Busy time is the union of operation intervals,
    averaged over the devices that ran any.  Idle gaps are labelled with
    the benchmark's host span that covers most of each gap.
    """
    planes = {p: evs for p, evs in events["device"].items() if evs}
    if not planes:
        return {"busy_s": 0.0, "window_s": 0.0, "ops": {}, "device_ops": [],
                "idle_gaps": []}
    if window is None:
        window = (min(s for evs in planes.values() for s, _, _ in evs),
                  max(s + d for evs in planes.values() for s, d, _ in evs))
    t0, t1 = window
    ops: dict = {}
    busy_ns = 0.0
    gaps = []
    for evs in planes.values():
        inside = []
        for s, d, name in evs:
            a, b = max(s, t0), min(s + d, t1)
            if b > a:
                inside.append((a, b))
                ops[name] = ops.get(name, 0.0) + (b - a) * 1e-9
        merged = _merge(inside)
        busy_ns += sum(b - a for a, b in merged)
        edges = [t0] + [x for ab in merged for x in ab] + [t1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
    busy_s = busy_ns * 1e-9 / len(planes)
    ops = {k: v / len(planes) for k, v in ops.items()}
    gaps.sort(key=lambda g: g[0] - g[1])
    idle = [[_label(events["host"], a, b), (b - a) * 1e-9]
            for a, b in gaps[:top]]
    device_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:top]
    return {"busy_s": busy_s, "window_s": (t1 - t0) * 1e-9, "ops": ops,
            "device_ops": [list(kv) for kv in device_ops],
            "idle_gaps": idle}


def _label(host: list, a: float, b: float) -> str:
    best, best_key = "no benchmark span", None
    for s, d, name in host:
        cover = min(s + d, b) - max(s, a)
        if cover <= 0:
            continue
        key = (cover, -d)           # most of the gap, then the innermost
        if best_key is None or key > best_key:
            best, best_key = name, key
    return best


def kernel_seconds(ops: dict, kernels) -> float:
    """Device seconds of the named kernels in a reduced ``ops`` table."""
    return sum(v for k, v in ops.items() if op_name(k) in kernels)
