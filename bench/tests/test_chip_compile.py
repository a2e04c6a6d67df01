"""Compile each cell's timed program for a TPU v5e chip that is described,
not attached, at the cell's real size, and read its memory analysis: the
qwen3-1.7b decode step at 8 slots x 2304 positions, and the 768^3 j3d27pt
sweep.  Nothing runs, so nothing here is a time.

The topology is described inside a module fixture, never at import: only
one process at a time may load the TPU library."""
import json
import os
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench.drivers import serve, stencil
from bench.reference.decoder import Dims

BENCH = Path(__file__).resolve().parents[1]
HBM = 16 * 2 ** 30                    # one v5e chip


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # such a compile is written to the persistent cache but cannot be read
    # back without a chip: keep it out
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture
def tpu_rows(monkeypatch):
    from repro.kernels import tuning
    monkeypatch.setattr(tuning, "on_tpu", lambda: True)


def _on(sharding, tree):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def _bytes(compiled):
    m = compiled.memory_analysis()
    out = {k: getattr(m, k) for k in (
        "argument_size_in_bytes", "output_size_in_bytes",
        "temp_size_in_bytes", "alias_size_in_bytes")}
    print(json.dumps(out))
    return out


def test_decode_step_fits_one_chip(one_chip, tpu_rows):
    from repro.models import model as M
    config = json.loads((BENCH / "configs" / "qwen3-1.7b.json").read_text())
    cfg = serve.arch_config(config)
    s = config["serving"]
    weights = jax.eval_shape(
        lambda k: serve.make_weights(k, Dims.of(config), cfg.padded_vocab),
        jax.random.PRNGKey(0))
    params = serve.program_params(weights)
    cache = jax.eval_shape(lambda: M.init_cache(
        cfg, s["slots"], s["max_seq"], dtype=jnp.dtype(s["cache_dtype"])))
    pos = jax.ShapeDtypeStruct((s["slots"],), jnp.int32)
    tok = jax.ShapeDtypeStruct((s["slots"], 1), jnp.int32)
    step = jax.jit(lambda p, c, pos, tok: M.decode_step(p, cfg, c, pos, tok))
    compiled = step.lower(_on(one_chip, params), _on(one_chip, cache),
                          _on(one_chip, pos), _on(one_chip, tok)).compile()
    m = _bytes(compiled)
    # weights 6.9 GB float32 in, the 2.11 GB bf16 cache in and out
    assert 6.8e9 < m["argument_size_in_bytes"] < 9.2e9
    assert 2.0e9 < m["output_size_in_bytes"] < 2.3e9
    # the scheduler holds the cache once more while it writes the new one
    # back: the step plus one cache must fit the chip
    assert sum(m.values()) - m["alias_size_in_bytes"] + 2.2e9 < HBM


def test_sweep_fits_one_chip(one_chip, tpu_rows):
    config = json.loads((BENCH / "configs" / "j3d27pt.json").read_text())
    spec = stencil.program_spec(config)
    n = config["interior"] + 2 * config["radius"]
    grid = jax.ShapeDtypeStruct((n, n, n), jnp.float32, sharding=one_chip)
    compiled = stencil.sweep_program(spec).lower(grid).compile()
    assert "tpu_custom_call" in compiled.as_text()
    m = _bytes(compiled)
    # the grid in the chip's (8, 128) tiling: 770 x 776 x 896 floats
    g = m["argument_size_in_bytes"]
    assert g >= 4 * n ** 3 and m["alias_size_in_bytes"] == g
    # the pad copy and the kernel's output, not a second grid
    assert m["temp_size_in_bytes"] < 2.2 * g
    # the donated grid, the temporaries and the two pairs of grids the
    # check keeps all fit the chip
    assert g + m["temp_size_in_bytes"] + 4 * g < HBM
