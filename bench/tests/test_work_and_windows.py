"""Work counts from shapes, the peak table, and end-to-end metrics taken over
every request or sweep of the window."""
import json
import math
from pathlib import Path

import numpy as np
import pytest

from bench import harness
from bench.drivers import serve, stencil
from bench.work import decoder as wd
from bench.work import stencil as ws

BENCH = Path(__file__).resolve().parents[1]
QWEN = json.loads((BENCH / "configs" / "qwen3-1.7b.json").read_text())
J3D = json.loads((BENCH / "configs" / "j3d27pt.json").read_text())


def test_decoder_work_counts():
    # 28 x (attention 12.58M + MLP 37.75M) + tied unembedding 311.2M
    assert wd.matmul_params(QWEN) == 28 * (2048 * 2048 * 2 + 2048 * 1024 * 2
                                           + 3 * 2048 * 6144) + 2048 * 151936
    assert wd.token_flops(QWEN, 0) == 2 * wd.matmul_params(QWEN) \
        + 4 * 28 * 16 * 128
    # a span is the sum of its tokens
    assert wd.span_flops(QWEN, 5, 9) == sum(wd.token_flops(QWEN, p)
                                            for p in range(5, 9))
    assert wd.span_flops(QWEN, 3, 3) == 0


def test_stencil_work_counts():
    assert ws.sweep_bytes(J3D) == 4 * (770 ** 3 + 768 ** 3)     # ~3.64 GB
    assert ws.sweep_flops(J3D) == 54 * 768 ** 3                 # ~24.5 GF
    peaks = harness.peaks("TPU v5 lite")
    assert math.isclose(ws.roofline_s(J3D, peaks),
                        4 * (770 ** 3 + 768 ** 3) / 819e9)


def test_stencil_taps_are_the_programs_scaled_to_sum_one():
    # the configuration states its taps; they are the program's j3d27pt,
    # each divided by their sum (about 2.92), in the same order
    from repro.core.stencils import STENCILS
    base = STENCILS["j3d27pt"]
    offsets, coeffs = stencil.taps(J3D)
    assert offsets == base.offsets
    assert math.isclose(sum(base.coeffs), 2.9185770477878896)
    scale = 1.0 / sum(base.coeffs)
    assert coeffs == tuple(c * scale for c in base.coeffs)
    assert math.isclose(sum(coeffs), 1.0)


def test_peaks_unknown_kind_raises():
    with pytest.raises(KeyError):
        harness.peaks("TPU v9 imaginary")
    v5e = harness.peaks("TPU v5 lite")
    assert v5e["bf16_flops_per_s"] == 197e12 and "Google Cloud" in v5e["source"]


def _serve_cell(records, t_open=10.0, t_close=20.0):
    c = serve.Cell.__new__(serve.Cell)
    c.config, c.records = QWEN, records
    c.t_open, c.t_close = t_open, t_close
    return c


def test_serving_tails_and_rates_cover_every_request():
    P = np.zeros(4, np.int32)
    recs = {
        # tokens before, inside and after the window
        0: serve.Served(0, P, times=[5.0, 9.0, 11.0, 12.0, 21.0]),
        1: serve.Served(1, P, times=[10.5, 19.5]),
        2: serve.Served(2, P, times=[1.0, 2.0]),   # never in it
    }
    c = _serve_cell(recs)
    assert sorted(c.itl_s()) == [1.0, 2.0, 9.0]     # gaps ending inside
    assert len(c.token_times()) == 4
    e2e = c.end_to_end()
    assert e2e["output_tok_per_s"] == 4 / 10.0
    assert e2e["itl_p95_ms"] == pytest.approx(
        1e3 * np.percentile([1.0, 2.0, 9.0], 95))
    assert {r.uid for r in c.in_window()} == {0, 1}


def test_call_ms_is_the_window_over_all_sweeps():
    c = stencil.Cell.__new__(stencil.Cell)
    c.t_open, c.t_close, c.sweeps = 3.0, 13.0, 200
    assert c.end_to_end()["call_ms"] == pytest.approx(50.0)
    c.untraced = (7.0, 140)
    assert c.untraced_call_ms() == pytest.approx(50.0)
