"""The benchmark's own tests: ``python -m pytest bench/tests`` from the repo
root.  They import the benchmark (``bench``) and the program (``src``)."""
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def pytest_configure(config):
    # the repo's conftest deselects tests under a "bench" node unless the
    # bench tier is asked for; collecting this directory is that request
    config.option.run_bench = True
