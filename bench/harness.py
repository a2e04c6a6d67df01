"""The benchmark's run of one cell: everything that is not a driver, a traffic
mix, a configuration or a metric reader.

``BENCHMARK.json`` names every part, and each is found by that name:

* ``bench/configs/<config>.json``: the configuration; its ``kind`` names the
  driver ``bench/drivers/<kind>.py``;
* ``bench/traffic/<traffic>.json``: the traffic mix's parameters;
* ``bench/metrics/<metric>.py``: one reader per per-layer metric, whose
  ``read(run)`` returns a number or None (nothing to read: left out).

A driver module has a class ``Cell(config, traffic, seed, devices,
seconds)`` with ``setup()``, ``run(seconds, tracer)`` (which sets ``t_open``
and ``t_close``, the window's ends on the host clock), ``end_to_end()``,
``counts()`` (attempted, failed), ``release()`` (frees the program's state)
and ``checks()``; see ``drivers/serve.py`` and ``drivers/stencil.py``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent

COMPILE_EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
LOWERING_EVENT = COMPILE_EVENTS[0]


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Check:
    """One number compared with the reference, beside its limit."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit     # NaN fails


@dataclasses.dataclass
class Spec:
    """One cell of ``BENCHMARK.json`` with its parts loaded; ``bench`` is the
    directory its drivers and metric readers are found in."""
    cell: dict
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench: Path = BENCH

    @property
    def name(self) -> str:
        return self.cell["name"]


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_spec(workload: str, root: Path = ROOT) -> Spec:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``, its parts read
    from ``<root>/bench``."""
    bench_json = root / "BENCHMARK.json"
    b = json.loads(bench_json.read_text())
    cells = {c["name"]: c for c in b["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in {bench_json}; have "
                       f"{sorted(cells)}")
    cell = cells[workload]
    conf = {c["name"]: c for c in b["configs"]}[cell["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{cell['traffic']}.json").read_text())
    return Spec(cell, config, traffic, *metrics_of(b, workload),
                root / "bench")


def metrics_of(b: dict, workload: str):
    """(end-to-end, per-layer) metrics of ``BENCHMARK.json`` content ``b``
    that the cell ``workload`` reports: those whose ``workloads`` list it,
    and those without the key (a per-layer one where the cell reports the
    end-to-end metric it moves)."""
    e2e = [m for m in b["end_to_end"] if _reports(m, workload)]
    names = {m["name"] for m in e2e}
    layer = [m for m in b["per_layer"]
             if (workload in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return e2e, layer


def _load(path: Path, what: str):
    if not path.is_file():
        raise FileNotFoundError(f"no {what} at {path}")
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def driver(kind: str, bench: Path = BENCH):
    """``<bench>/drivers/<kind>.py``: the driver of a kind of deployment."""
    return _load(bench / "drivers" / f"{kind}.py", f"driver {kind!r}")


def metric_reader(name: str, bench: Path = BENCH):
    """``read`` of ``<bench>/metrics/<name>.py``."""
    return _load(bench / "metrics" / f"{name}.py", f"metric {name!r}").read


def require_chips(chips: int):
    """The devices the cell runs on; raises :class:`NoChip` without a TPU."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoChip(f"JAX could not start a backend: {e}") from e
    if devices[0].platform != "tpu":
        raise NoChip(f"JAX found platform {devices[0].platform!r}, not a "
                     f"TPU; the benchmark has no fallback")
    if len(devices) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(devices)}")
    return devices[:chips]


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip of this kind; an unknown kind is an
    error, not a default."""
    table = json.loads((BENCH / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r} in bench/peaks.json")
    return table[device_kind]


class CompileLog:
    """JAX's own compile events, each with the host time it ended at:
    lowering and compiling (a persistent-cache read included)."""

    def __init__(self):
        import jax
        self.events: List[tuple] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_):
        if event in COMPILE_EVENTS:
            self.events.append((time.monotonic(), event, duration))

    def seconds_before(self, t: float) -> float:
        return sum(d for at, _, d in self.events if at < t)

    def lowered_between(self, t0: float, t1: float) -> int:
        return sum(1 for at, e, _ in self.events
                   if e == LOWERING_EVENT and t0 <= at <= t1)


def enable_cache() -> str:
    """The program's persistent compile cache, inside the checkout unless
    ``JAX_COMPILATION_CACHE_DIR`` says otherwise, storing every program."""
    import jax
    from repro.runtime.compile_cache import enable_compile_cache
    where = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return where


@dataclasses.dataclass
class Run:
    """What the metric readers read: the cell's driver object with its
    records, the reduced trace (or None), the chip's peaks and the compile
    seconds of set-up."""
    spec: Spec
    cell: Any
    trace: Any
    peaks: dict
    setup_compile_s: float


def memory_peak(devices) -> int:
    """The process's peak of device memory on the fullest chip."""
    return int(max((x.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for x in devices))


def device_record(devices, trace=None) -> dict:
    d = devices[0]
    out = {"platform": d.platform, "kind": d.device_kind,
           "count": len(devices), "memory_peak_bytes": memory_peak(devices)}
    if trace is not None:
        out["busy_s"] = trace.busy_s
        out["window_s"] = trace.window_s
    return out


def run_cell(spec: Spec, seed: int, seconds: float, trace: bool, *,
             t_process: float, devices=None, trace_s: float = 3.0,
             log=print) -> dict:
    """Set up, measure, check and reduce one run; returns the result line.

    ``devices`` None means: require the cell's chips (the benchmark's own
    runs); tests pass the CPU devices to drive everything but that look."""
    t_backend = time.monotonic()
    if devices is None:
        devices = require_chips(spec.cell["chips"])
    kind = devices[0].device_kind
    chip_peaks = peaks(kind) if devices[0].platform == "tpu" else {}
    compiles = CompileLog()
    cell = driver(spec.config["kind"], spec.bench).Cell(
        spec.config, spec.traffic, seed, devices, seconds)
    t_build = time.monotonic()
    cell.setup()
    t_built = time.monotonic()
    log(f"set-up phases (s): imports {t_backend - t_process!r}, backend "
        f"{t_build - t_backend!r}, data and warm-up {t_built - t_build!r}; "
        f"device memory peak {memory_peak(devices)}", file=sys.stderr)
    tracer = None
    with tempfile.TemporaryDirectory(prefix="bench_trace_") as tdir:
        if trace and devices[0].platform == "tpu":
            # only the chip's own trace gives device time: elsewhere the
            # device metrics are left out, never read from the CPU
            from bench.trace import Tracer
            tracer = Tracer(tdir, min(trace_s, seconds / 2))
        cell.run(seconds, tracer)
        setup_s = cell.t_open - t_process
        setup_compile_s = compiles.seconds_before(cell.t_open)
        window_compiles = compiles.lowered_between(cell.t_open, cell.t_close)
        log(f"programs lowered inside the window: {window_compiles}",
            file=sys.stderr)
        reduced = None
        if tracer is not None:
            from bench.trace import reduce
            reduced = reduce(tdir, len(devices))
    dev = device_record(devices, reduced)
    e2e = cell.end_to_end()
    cell.release()
    checks = cell.checks()
    run = Run(spec, cell, reduced, chip_peaks, setup_compile_s)
    metrics: Dict[str, dict] = {}
    if trace:
        for m in spec.per_layer:
            v = metric_reader(m["name"], spec.bench)(run)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        e2e["setup_s"] = setup_s
        for m in spec.end_to_end:
            metrics[m["name"]] = {"value": float(e2e[m["name"]]),
                                  "unit": m["unit"]}
    attempted, failed = cell.counts()
    correct = bool(checks) and all(c.ok for c in checks) and failed == 0
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": dev}
    if reduced is not None:
        out["breakdown"] = reduced.breakdown()
    out["checks"] = {c.name: {"value": c.value if math.isfinite(c.value)
                              else None, "limit": c.limit} for c in checks}
    for c in checks:
        log(f"check {c.name}: {c.value!r} (limit {c.limit!r}) "
            f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr)
    return out
