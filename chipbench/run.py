"""The chip benchmark's command.

    python chipbench/run.py --workload <name> --seed <n> --seconds <s> \
        --trace <0|1>

Runs one cell of ``BENCHMARK.json`` on the chips of this machine (see
``harness.py``).  The last line of standard output is the result as one
JSON object; the numbers ``correct`` compared, each with its limit, are
the last lines of standard error.  With no TPU, an unknown one, or too few
chips it exits non-zero and prints no result.
"""
from __future__ import annotations

import time

T_START = time.time()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    import harness
    import spec

    cell = spec.load_cell(args.workload)
    try:
        out = harness.run(cell, args.seed, args.seconds, bool(args.trace),
                          t_start=T_START,
                          log=lambda s: print(s, file=sys.stderr, flush=True))
    except harness.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 4
    print(json.dumps(out), flush=True)
    for name, c in out["checks"].items():
        print(f"check {name} {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
