"""The serving cell's readings of the scheduler's own spans and host-sync
counter, on the CPU at a tiny size: numbers from a traced run of the
program, and nothing (no reading, no error) from a program whose scheduler
records no such spans."""
import types

import pytest

from bench import harness
from test_drivers import _run, tiny_serve

SPAN_METRICS = ("serve.sample_ms", "serve.writeback_ms",
                "serve.host_syncs_per_step")


def test_traced_run_reads_the_scheduler_spans():
    out = _run(tiny_serve(), trace=True)
    for name in SPAN_METRICS:
        assert out["metrics"][name]["value"] >= 0, name
    # depth 0 on 4 busy slots: the logits' block, the health fetch and one
    # token fetch a row; an admission in the window adds its own
    assert out["metrics"]["serve.host_syncs_per_step"]["value"] >= 2 + 4


@pytest.mark.parametrize("name", SPAN_METRICS)
def test_no_spans_no_reading(name):
    cell = types.SimpleNamespace(window_stats=lambda phase: [])
    run = harness.Run(spec=None, cell=cell, trace=None, peaks={},
                      setup_compile_s=0.0)
    assert harness.metric_reader(name)(run) is None
