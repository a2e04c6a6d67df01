"""Benchmark-harness smoke (the ``bench`` tier, enable with --run-bench).

One tiny sweep point per op in interpret mode, so the ``sweep_tiles``
harness (and its ``tuning.register`` wiring + JSON artifact schema) cannot
bit-rot without CI noticing.  register=False keeps the process-global
tuning table untouched for any tests that follow.
"""
import json
import os
import sys

import pytest

pytestmark = pytest.mark.bench

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def sweep_results():
    sys.path.insert(0, REPO_ROOT)  # benchmarks/ is not a package on sys.path
    try:
        from benchmarks import sweep_tiles
    finally:
        sys.path.pop(0)
    return sweep_tiles.run(smoke=True, register=False)


def test_sweep_smoke_points_are_bit_identical(sweep_results):
    spmm = sweep_results["spmm"]
    assert spmm["points"], "sweep produced no points"
    assert all(p["bit_identical"] for p in spmm["points"])
    assert not spmm["registered"]
    # the residency invariant: more resident tiles, fewer stream walks
    by_nt = {p["nt"]: p["stream_walks"] for p in spmm["points"]
             if p["bn"] == spmm["points"][0]["bn"]}
    if len(by_nt) > 1:
        assert by_nt[max(by_nt)] < by_nt[min(by_nt)]


def test_sweep_smoke_flash_points(sweep_results):
    """The flash (bq, bk) sweep: every dense and sparse point oracle-parity,
    winner carries both the sparse and dense tile picks, nothing
    registered."""
    fl = sweep_results["flash"]
    assert fl["dense_points"] and fl["sparse_points"]
    assert all(p["parity"] for p in fl["points"])
    assert not fl["registered"]
    assert {"bq", "bk", "dense_bq", "dense_bk"} <= set(fl["winner"])
    S = fl["shape"]["S"]
    for p in fl["sparse_points"]:
        # the window walk is structurally below the dense grid
        assert p["walked_tiles"] < (S // p["bq"]) * (S // p["bk"]) or \
            p["walked_tiles"] <= 8  # tiny smoke grids bottom out at the bucket floor


def test_emit_bench_schema(tmp_path, sweep_results):
    from benchmarks.common import emit_bench

    path = emit_bench("smoke_test", sweep_results, directory=str(tmp_path))
    with open(path) as f:
        doc = json.load(f)
    assert doc["bench"] == "smoke_test"
    assert {"backend", "device_count", "jax_version"} <= set(doc)
    assert doc["spmm"]["points"]


@pytest.fixture(scope="module")
def attention_results():
    sys.path.insert(0, REPO_ROOT)
    try:
        from benchmarks import bench_attention
    finally:
        sys.path.pop(0)
    return bench_attention.run(smoke=True)


def test_bench_attention_smoke(attention_results):
    """The sparse-vs-dense attention bench: every point bit-identical to the
    dense-masked kernel, walked-tile counts below the dense grid, schema
    stable for the BENCH_attention.json artifact."""
    assert attention_results["points"]
    for p in attention_results["points"]:
        assert p["parity_bit_identical"] is True
        assert p["walked_tiles"] <= p["walked_tiles_bucketed"]
        assert p["walked_tiles_bucketed"] <= p["dense_tiles"]
        assert p["t_dense_us"] > 0 and p["t_sparse_us"] > 0
        assert p["speedup"] > 0
    # windows are increasing fractions -> walked tiles monotone nondecreasing
    walked = [p["walked_tiles"] for p in attention_results["points"]]
    assert walked == sorted(walked)


@pytest.fixture(scope="module")
def precision_results():
    sys.path.insert(0, REPO_ROOT)
    try:
        from benchmarks import bench_precision
    finally:
        sys.path.pop(0)
    return bench_precision.sweep(smoke=True)


def test_bench_precision_smoke(precision_results):
    """The narrow-precision sweep measures all three quantized dtypes and
    the BlockQuant contracts hold at bench shapes too: every quantized spmm
    point is bit-identical to its dequantize-then-f32 reference, and the
    int8 serving row reproduces the f32 loop's greedy tokens exactly."""
    spmm = precision_results["spmm"]
    for name in ("fp8_e4m3", "fp8_e5m2", "int8"):
        p = spmm["points"][name]
        assert p["bit_identical_vs_dequant_ref"] is True
        assert p["time_us"] > 0
        assert p["rel_err"] < 0.1
    assert spmm["points"]["f32"]["max_abs_err"] == 0.0
    serving = precision_results["serving"]
    assert serving["int8"]["tokens_match_frac"] == 1.0
    for name in ("fp8_e4m3", "fp8_e5m2", "int8"):
        assert serving[name]["first_decode_logit_rel_err"] < 0.2
