#!/usr/bin/env python3
"""Run one benchmark cell once and print its result as the last line.

    python3 bench/run.py --workload deep1m-ed-f32.svc32 --seed 7 \\
        --seconds 45 --trace 0

Run from the root of a checkout that holds the program under ``src/``.
Exits non-zero, with no result line, where JAX finds no TPU or fewer
chips than the cell asks for.
"""

import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
# Run as a script, Python puts bench/ itself first on the path, where its
# modules would shadow top-level ones; the checkout's root goes there.
if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
    sys.path[0] = str(ROOT)
elif str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())
