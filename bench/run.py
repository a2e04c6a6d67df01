#!/usr/bin/env python3
"""Run one benchmark cell once, on the chips of the machine it starts on.

    python3 bench/run.py --workload qwen3-1.7b.reasoning --seed 7 \\
        --seconds 40 --trace 0

Loads the cell's configuration and traffic from ``BENCHMARK.json``, makes
weights or data from ``--seed`` on the device, warms up every shape the
window uses (set-up), measures for ``--seconds``, checks what the timed path
produced against a plain reference, and prints one JSON line last on
standard output.  ``--trace 0`` reports the cell's end-to-end metrics,
``--trace 1`` its per-layer metrics, read partly from a profiler trace of
the window's last seconds.  Without a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from bench import harness
    spec = harness.load_spec(args.workload)
    harness.enable_cache()
    try:
        out = harness.run_cell(spec, args.seed, args.seconds,
                               bool(args.trace), t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"bench: no chip to run on: {e}", file=sys.stderr, flush=True)
        return 2
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
