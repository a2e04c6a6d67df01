"""Repo-wide pytest configuration.

Two jobs, both of which must happen before any test module imports jax:

1. Force a multi-device CPU topology (4 virtual devices) so the sharded
   sparse-engine tests exercise real ``shard_map`` partitioning on a plain
   CPU host.  Harmless for single-device tests: jit still places
   un-sharded computations on device 0.
2. Tier the suite: ``slow`` (integration / model-smoke) and ``serve``
   (full serving-loop smoke) tests are deselected by default so the tier-1
   gate (``pytest -x -q``) finishes in minutes; run them with
   ``--run-slow`` / ``--run-serve`` (or select explicitly with ``-m``).
"""
import os

# Must precede the first jax backend initialization (which happens at test
# collection time via module-level PRNGKey calls in some test files).
if "xla_force_host_platform_device_count" not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count=4"
    ).strip()

import pytest  # noqa: E402


def pytest_addoption(parser):
    parser.addoption(
        "--run-slow", action="store_true", default=False,
        help="run tests marked slow (integration / model smoke)")
    parser.addoption(
        "--run-serve", action="store_true", default=False,
        help="run tests marked serve (full serving-loop smoke)")
    parser.addoption(
        "--run-bench", action="store_true", default=False,
        help="run tests marked bench (benchmark-harness smoke)")
    parser.addoption(
        "--run-stress", action="store_true", default=False,
        help="run tests marked stress (randomized fault/eviction "
             "resilience runs)")


def pytest_collection_modifyitems(config, items):
    # Explicit opt-ins override the default deselection: --run-slow, a -m
    # marker expression, or directly naming a file / node id on the CLI
    # (`pytest tests/test_models_smoke.py::test_x` should run that test,
    # not report a green 0-test run).
    named_explicitly = any(
        arg.endswith(".py") or "::" in arg for arg in config.args)
    if config.getoption("-m") or named_explicitly:
        return
    # slow, serve, bench, and stress are independently opt-in tiers
    skip_marks = {m for m, opt in (("slow", "--run-slow"),
                                   ("serve", "--run-serve"),
                                   ("bench", "--run-bench"),
                                   ("stress", "--run-stress"))
                  if not config.getoption(opt)}
    selected = [i for i in items
                if not any(m in i.keywords for m in skip_marks)]
    deselected = [i for i in items
                  if any(m in i.keywords for m in skip_marks)]
    if deselected:
        config.hook.pytest_deselected(items=deselected)
        items[:] = selected
