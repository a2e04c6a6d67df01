"""The reduction from a profiler trace to device time, on synthetic events
and on a small trace recorded on a TPU v5e (a 768^3 j3d27pt Jacobi run's
traced window, kept in ``data/``)."""
import glob
from pathlib import Path

import pytest

from bench import trace as T

DATA = Path(__file__).resolve().parent / "data"
MS = 1_000_000


def _spans(w0, w1, extra=()):
    return [(T.OPEN, w0 - MS, MS), (T.CLOSE, w1, MS), *extra]


def test_busy_is_the_union_of_op_intervals_inside_the_window():
    ops = [("%a = f32[8]{0} add(f32[8]{0} %x)", 0, 20 * MS),      # half in
           ("%b = f32[8]{0} add(f32[8]{0} %x)", 30 * MS, 20 * MS),
           ("%c = f32[8]{0} copy(f32[8]{0} %x)", 40 * MS, 20 * MS),  # overlap
           ("%d = f32[8]{0} copy(f32[8]{0} %x)", 95 * MS, 20 * MS)]  # half out
    r = T.reduce_events([ops], _spans(10 * MS, 100 * MS))
    assert r.window_s == pytest.approx(0.090)
    assert r.busy_s == pytest.approx((10 + 30 + 5) * 1e-3)
    assert r.idle_pct == pytest.approx(100 * (1 - 45 / 90))
    assert r.op_seconds["add f32[8]"] == pytest.approx(0.030)
    assert r.op_seconds["copy f32[8]"] == pytest.approx(0.025)


def test_gaps_are_labelled_by_the_host_span_covering_them():
    ops = [("%k = f32[8]{0} add(f32[8]{0} %x)", 0, 10 * MS),
           ("%k = f32[8]{0} add(f32[8]{0} %x)", 50 * MS, 10 * MS)]
    spans = _spans(0, 100 * MS, [("bench.admit", 12 * MS, 30 * MS),
                                 ("bench.decode_step", 60 * MS, 40 * MS)])
    r = T.reduce_events([ops], spans)
    assert sorted(r.gaps) == [("bench.admit", pytest.approx(0.040)),
                              ("bench.decode_step", pytest.approx(0.040))]
    top = r.breakdown()
    assert top["device_ops"][0][0] == "add f32[8]"
    assert len(top["idle_gaps"]) <= 10


def test_kernel_time_counts_whole_mosaic_calls_only():
    k = ('%apply.1 = f32[8]{0} custom-call(f32[9]{0} %p), '
         'custom_call_target="tpu_custom_call"')
    ops = [(k, 5 * MS, 10 * MS), (k, 20 * MS, 10 * MS),
           (k, 95 * MS, 10 * MS)]                     # cut by the window
    r = T.reduce_events([ops], _spans(10 * MS, 100 * MS))
    assert r.kernel_calls == 1
    assert r.kernel_seconds == pytest.approx(0.010)
    assert "custom-call apply" in r.op_seconds


def test_busy_is_averaged_over_devices():
    a = [("%x = f32[8]{0} add(f32[8]{0} %y)", 0, 50 * MS)]
    r = T.reduce_events([a, []], _spans(0, 100 * MS))
    assert r.busy_s == pytest.approx(0.025)


def test_a_trace_without_window_markers_is_refused():
    with pytest.raises(ValueError):
        T.reduce_events([[]], [("bench.admit", 0, MS)])


def test_recorded_chip_trace():
    files = glob.glob(str(DATA / "**" / "*.xplane.pb"), recursive=True)
    assert files, "the recorded trace is missing"
    assert sum(Path(f).stat().st_size for f in files) < 1_000_000
    r = T.reduce(str(DATA), 1)
    assert 0 < r.busy_s <= r.window_s
    assert r.kernel_calls >= 1
    assert r.kernel_seconds / r.kernel_calls > 1e-3   # a 768^3 sweep's kernel
    assert any(k.startswith("custom-call") for k in r.op_seconds)
    assert {label for label, _ in r.gaps} <= {"bench.sweeps", "bench.sync",
                                              "host:none"}
