#!/usr/bin/env python3
"""Readings that set a cell's limit: the program's and the control's.

    python3 bench/control.py --workload j3d27pt.jacobi --seeds 1,2,3 \\
        --seconds 40

For each seed, in one process, it sets the cell up and runs its window as
``bench/run.py`` does, then reads the number the cell compares twice: for
what the program produced, and for the control, the plain reference in the
precision below the configuration's put in the program's place (float8
e4m3 products for a model that computes in bfloat16; a bfloat16 sweep for a
float32 stencil).  One JSON line per seed.  The largest program reading
over a dozen seeds or more is the limit's lower end, the smallest control
reading its upper end.  The benchmark's own runs never run the control.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def readings(cell) -> dict:
    """{"program": x, "control": y} for a cell whose window has run."""
    if hasattr(cell, "gaps"):
        return {"program": float(cell.gaps().max()),
                "control": float(cell.gaps(control=True).max())}
    return {"program": cell.errors(),
            "control": cell.errors(dtype="bfloat16")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    from bench import harness
    spec = harness.load_spec(args.workload)
    harness.enable_cache()
    try:
        devices = harness.require_chips(spec.cell["chips"])
    except harness.NoChip as e:
        print(f"bench: no chip to run on: {e}", file=sys.stderr, flush=True)
        return 2
    kind = harness.driver(spec.config["kind"], spec.bench)
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.monotonic()
        cell = kind.Cell(spec.config, spec.traffic, seed, devices,
                         args.seconds)
        cell.setup()
        cell.run(args.seconds, None)
        cell.release()
        out = {"workload": spec.name, "seed": seed, **readings(cell),
               "wall_s": time.monotonic() - t0}
        print(json.dumps(out), flush=True)
        del cell
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
