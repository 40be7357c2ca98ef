#!/usr/bin/env python3
"""The benchmark's one command: one run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload ssb-sf10.drill --seed 7 --seconds 30 --trace 0

The cell's configuration makes the data from ``--seed``; its traffic mix
makes the warm-up and the window's requests.  Set-up (data, upload, programs
from the compilation cache in ``.jax_cache``, warm-up) is timed as
``setup_s``; then the window drives ``CacheService.submit_batch`` for
``--seconds``.  Afterwards the answers of a sample of the window's intents,
drawn from the seed, are compared with the plain numpy reference.  With
``--trace 0`` the result holds the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics, read from a profiler trace of the
window.  The last line of stdout is one JSON object.

It exits non-zero, and prints no result, without a TPU, with fewer chips
than the cell asks for, on a device missing from ``bench/peaks.json``, or
without the program's ``src/`` beside ``bench/``.

``--control bfloat16`` puts the reference computed at bfloat16 in the
program's place in the comparison of answers, and ``--control float8`` (a
cell with a model) the reference model with its weight matrices rounded to
float8 in the program's place in the comparison of logits, so that
``correct`` comes out false; the program's own reading is printed beside it
(the readings of the limits).
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("bfloat16", "float8"), default=None)
    args = ap.parse_args(argv)
    try:
        out = harness.run(args.workload, args.seed, args.seconds, bool(args.trace),
                          T_START, control=args.control)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
