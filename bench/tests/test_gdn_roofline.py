"""``gdn.decode_roofline`` on reduced traces made by hand: it reads the
kernel's share where the Mosaic kernels of the window are ``gdn_decode``'s,
and None where another kernel ran or none did."""
from types import SimpleNamespace

import pytest

from bench import harness
from bench.trace import Reduced
from bench.work import qwen3_next as work

OP = "custom-call gdn_decode"
read = harness.metric_reader("gdn.decode_roofline")
SPEC = harness.load_spec("qwen3-next-80b-a3b.longgen")
LEAST = work.gdn_decode_bytes(SPEC.config, 64) / 819e9   # one call


def _run(op_seconds, kernel_seconds, calls):
    t = Reduced(window_s=3.0, busy_s=2.0, op_seconds=op_seconds,
                kernel_seconds=kernel_seconds, kernel_calls=calls, gaps=[])
    return SimpleNamespace(spec=SPEC, trace=t,
                           peaks={"hbm_bytes_per_s": 819e9})


def test_reads_the_share_of_the_whole_calls():
    # 60 calls inside the window at twice the least time, and part of one
    # more cut by the window's end
    whole = 60 * 2 * LEAST
    assert read(_run({OP: whole + LEAST}, whole, 60)) == pytest.approx(50.0)


def test_none_when_another_kernel_ran():
    whole = 60 * 2 * LEAST
    assert read(_run({OP: whole - LEAST}, whole, 61)) is None


def test_none_without_the_kernel_or_a_trace():
    assert read(_run({"fusion f32[8]": 1.0}, 0.5, 3)) is None
    assert read(SimpleNamespace(spec=SPEC, trace=None, peaks={})) is None
