"""Markdown delta table between two BENCH_*.json artifacts.

    python benchmarks/bench_delta.py PREV.json CURRENT.json

Reads the ``benchmarks.run --json`` payloads, joins rows on
``(bench, name)``, and prints a GitHub-flavored markdown table of
us/call and qps deltas — CI appends it to the job summary so perf
regressions are visible at review time without downloading artifacts.
The script never fails the job: any malformed input degrades to a note
(the delta is advisory; the artifacts remain the source of truth).
"""

from __future__ import annotations

import json
import sys

# us/call swings below this are timer noise on shared CI runners; the
# table marks larger ones so reviewers scan only the meaningful lines.
NOISE_PCT = 10.0


def _rows(path):
    with open(path) as f:
        payload = json.load(f)
    rows = {}
    for r in payload.get("rows", []):
        rows[(r["bench"], r["name"])] = r
    return payload, rows


def _fmt_pct(pct):
    mark = " ⚠" if abs(pct) >= NOISE_PCT else ""
    return f"{pct:+.1f}%{mark}"


def main(argv) -> int:
    if len(argv) != 3:
        print("usage: bench_delta.py PREV.json CURRENT.json",
              file=sys.stderr)
        return 0                       # advisory: never fail the job
    try:
        prev_payload, prev = _rows(argv[1])
        cur_payload, cur = _rows(argv[2])
    except (OSError, ValueError, KeyError) as e:
        print(f"bench delta unavailable: {e}")
        return 0

    print("## Benchmark delta vs previous push")
    print()
    print(f"prev: scale={prev_payload.get('scale')} "
          f"wall={prev_payload.get('wall_seconds')}s "
          f"failures={len(prev_payload.get('failures', []))} · "
          f"current: scale={cur_payload.get('scale')} "
          f"wall={cur_payload.get('wall_seconds')}s "
          f"failures={len(cur_payload.get('failures', []))}")
    print()
    print("| bench | name | prev us | cur us | Δus | prev qps | cur qps |")
    print("|---|---|---:|---:|---:|---:|---:|")
    for key in sorted(set(prev) | set(cur)):
        b, n = key
        p, c = prev.get(key), cur.get(key)
        if p is None or c is None:
            status = "added" if p is None else "removed"
            print(f"| {b} | {n} | — | — | {status} | — | — |")
            continue
        try:
            pu, cu = float(p["us_per_call"]), float(c["us_per_call"])
            pct = 100.0 * (cu - pu) / pu if pu else 0.0
            pq = (p.get("derived") or {}).get("qps", "—")
            cq = (c.get("derived") or {}).get("qps", "—")
            print(f"| {b} | {n} | {pu:.0f} | {cu:.0f} | {_fmt_pct(pct)} "
                  f"| {pq} | {cq} |")
        except (KeyError, TypeError, ValueError):
            # Schema drift in one artifact must not break the summary.
            print(f"| {b} | {n} | — | — | malformed row | — | — |")
    print()
    print(f"(Δus ⚠ marks swings ≥ {NOISE_PCT:.0f}%; positive = slower. "
          "Non-blocking — artifacts are the source of truth.)")
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv))
