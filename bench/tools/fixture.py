#!/usr/bin/env python3
"""Cut a small piece of a recorded trace into the plain format of
``bench.harness.trace``, for the reduction's test fixture:

    python3 bench/tools/fixture.py <trace_dir> <out.json> [max_ops]
"""
from __future__ import annotations

import json
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.harness import trace as T  # noqa: E402
from bench.harness.context import SPANS  # noqa: E402


def main(argv=None) -> int:
    argv = argv if argv is not None else sys.argv[1:]
    src, out = argv[0], argv[1]
    max_ops = int(argv[2]) if len(argv) > 2 else 120
    t = T.load(src, SPANS)
    dev = t["devices"][0]
    ops = dev["ops"][:max_ops]
    lo, hi = ops[0][1], ops[-1][1] + ops[-1][2]
    piece = {"devices": [{
        "name": dev["name"], "ops": [list(o) for o in ops],
        "modules": [m for m in dev["modules"]
                    if m[1] + m[2] > lo and m[1] < hi]}],
        "spans": [s for s in t["spans"]
                  if s[1] + s[2] > lo and s[1] < hi
                  and s[0] != T.WINDOW_SPAN],
        "window": [lo, hi]}
    Path(out).write_text(json.dumps(piece))
    return 0


if __name__ == "__main__":
    sys.exit(main())
