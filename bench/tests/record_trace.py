#!/usr/bin/env python3
"""Record the small chip trace that ``test_trace.py`` reads (run on a TPU):

    python3 bench/tests/record_trace.py [OUT_DIR]

Runs the ``j3d27pt.jacobi`` cell for one second with the last 0.3 s traced,
and keeps only the ``.xplane.pb`` (tens of KB) under ``OUT_DIR``, by default
``bench/tests/data/``.
"""
import glob
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
DATA = Path(__file__).resolve().parent / "data"


def main() -> int:
    from bench import harness
    from bench.trace import Tracer
    spec = harness.load_spec("j3d27pt.jacobi")
    harness.enable_cache()
    devices = harness.require_chips(1)
    cell = harness.driver("stencil").Cell(spec.config, spec.traffic, 1,
                                          devices, 1.0)
    cell.setup()
    out = Path(sys.argv[1]) if len(sys.argv) > 1 else DATA
    shutil.rmtree(out, ignore_errors=True)
    cell.run(1.0, Tracer(str(out / "jacobi"), 0.3))
    for f in glob.glob(str(out / "**" / "*"), recursive=True):
        if os.path.isfile(f) and not f.endswith(".xplane.pb"):
            os.remove(f)
    print("trace kept:", [(f, os.path.getsize(f)) for f in glob.glob(
        str(out / "**" / "*.xplane.pb"), recursive=True)])
    return 0


if __name__ == "__main__":
    sys.exit(main())
