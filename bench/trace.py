"""Profiler trace of a short window, and its reduction to device time.

:class:`Tracer` starts JAX's profiler a fixed time before the measured window
closes and stops it after the window's last device sync; the benchmark's own
host spans (``jax.profiler.TraceAnnotation``) land in the same trace.
:func:`reduce` reads the ``.xplane.pb`` it writes with nothing but JAX:

* device busy time is the union of the intervals of the ops on each device
  plane's ``XLA Ops`` line, clipped to the traced window;
* the window runs from the end of the ``bench.trace_open`` span to the start
  of the ``bench.trace_close`` span, on the trace's own clock (host and
  device events share it);
* each idle gap between busy intervals is labelled by the benchmark span
  that covers most of it on the host (``host:none`` where none does).
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import time
from typing import Dict, List, Optional, Tuple

OPEN, CLOSE = "bench.trace_open", "bench.trace_close"
SPAN_PREFIX = "bench."
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'   # a Mosaic kernel


class Tracer:
    """Traces the last ``length_s`` seconds of a window that closes at
    ``close_at`` (host monotonic seconds).  The driver's loop calls
    :meth:`tick` once per iteration and :meth:`finish` after its last sync."""

    def __init__(self, log_dir: str, length_s: float):
        self.log_dir, self.length_s = log_dir, length_s
        self.close_at: Optional[float] = None
        self.t_start = self.t_stop = None

    @property
    def active(self) -> bool:
        return self.t_start is not None and self.t_stop is None

    def arm(self, close_at: float) -> None:
        self.close_at = close_at

    def tick(self, now: float) -> None:
        if (self.t_start is None and self.close_at is not None
                and now >= self.close_at - self.length_s):
            import jax
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 2
            jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            with jax.profiler.TraceAnnotation(OPEN):
                self.t_start = time.monotonic()

    def finish(self) -> None:
        if not self.active:
            return
        import jax
        with jax.profiler.TraceAnnotation(CLOSE):
            self.t_stop = time.monotonic()
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Reduced:
    """What the metric readers take from one trace."""
    window_s: float
    busy_s: float                      # mean over the devices read
    op_seconds: Dict[str, float]       # device op name -> seconds
    kernel_seconds: float              # Mosaic kernels (tpu_custom_call)
    kernel_calls: int
    gaps: List[Tuple[str, float]]      # (label, seconds), longest first

    @property
    def idle_pct(self) -> float:
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def breakdown(self, n: int = 10) -> dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:n]
        gaps = collections.defaultdict(float)
        for label, s in self.gaps:
            gaps[label] += s
        top = sorted(gaps.items(), key=lambda kv: -kv[1])[:n]
        return {"device_ops": [[k, v] for k, v in ops],
                "idle_gaps": [[k, v] for k, v in top]}


def op_name(event_name: str) -> str:
    """A device op's stable name: its HLO opcode and result type, without
    the instruction's numbered name (``%copy.2 = f32[8]{0} copy(...)`` ->
    ``copy f32[8]``); a tuple-typed result is named ``tuple``, and a Mosaic
    kernel keeps the name of the function that called it (``custom-call
    apply``)."""
    inst, sep, rest = event_name.partition(" = ")
    if not sep:
        return event_name.split("(")[0][:80]
    if rest.startswith("("):                 # tuple type: skip to its end
        depth = 0
        for i, ch in enumerate(rest):
            depth += {"(": 1, ")": -1}.get(ch, 0)
            if depth == 0:
                break
        shape, rest = "tuple", rest[i + 1:].lstrip()
    else:
        shape, _, rest = rest.partition(" ")
        shape = shape.split("{")[0]
    opcode = rest.split("(")[0]
    if opcode == "custom-call":
        return f"custom-call {inst.lstrip('%').split('.')[0]}"
    return f"{opcode} {shape}"


def _union(intervals: List[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def reduce_events(device_ops: List[List[Tuple[str, int, int]]],
                  host_spans: List[Tuple[str, int, int]]) -> Reduced:
    """Reduce ``(name, start_ns, duration_ns)`` events: one list of ops per
    device, and the host's benchmark spans, the window markers among them."""
    opens = [s + d for n, s, d in host_spans if n == OPEN]
    closes = [s for n, s, d in host_spans if n == CLOSE]
    if not opens or not closes:
        raise ValueError("trace has no bench.trace_open/close markers")
    w0, w1 = opens[0], closes[-1]
    if w1 <= w0:
        raise ValueError(f"empty traced window ({w0} .. {w1} ns)")
    op_s: Dict[str, float] = collections.defaultdict(float)
    busy, kern_s, kern_n = [], 0.0, 0
    for ops in device_ops:
        clipped = []
        for name, s, d in ops:
            a, b = max(s, w0), min(s + d, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            op_s[op_name(name)] += (b - a) * 1e-9
            if KERNEL_MARK in name and s >= w0 and s + d <= w1:
                kern_s += d * 1e-9
                kern_n += 1
        busy.append(_union(clipped))
    n_dev = max(len(device_ops), 1)
    busy_s = sum((b - a) for iv in busy for a, b in iv) * 1e-9 / n_dev
    spans = [(n, s, s + d) for n, s, d in host_spans
             if n not in (OPEN, CLOSE) and s + d > w0 and s < w1]
    gaps = []
    for iv in busy[:1]:            # gaps are read on the first device
        edges = [w0] + [x for ab in iv for x in ab] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b > a:
                gaps.append((_label(spans, a, b), (b - a) * 1e-9))
    gaps.sort(key=lambda g: -g[1])
    return Reduced(window_s=(w1 - w0) * 1e-9, busy_s=busy_s,
                   op_seconds=dict(op_s), kernel_seconds=kern_s,
                   kernel_calls=kern_n, gaps=gaps)


def _label(spans, a: int, b: int) -> str:
    best, cover = "host:none", 0
    for name, s, e in spans:
        c = min(e, b) - max(s, a)
        if c > cover:
            best, cover = name, c
    return best


def read_xplane(path: str, n_devices: int):
    """(device op lists, host benchmark spans) from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    device_ops, spans = [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            idx = int(plane.name.rsplit(":", 1)[1])
            if idx >= n_devices:
                continue
            ops = []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.name, int(e.start_ns), int(e.duration_ns))
                               for e in line.events)
            device_ops.append(ops)
        elif plane.name == "/host:CPU":
            for line in plane.lines:
                spans.extend((e.name, int(e.start_ns), int(e.duration_ns))
                             for e in line.events
                             if e.name.startswith(SPAN_PREFIX))
    return device_ops, spans


def reduce(log_dir: str, n_devices: int) -> Reduced:
    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(files) != 1:
        raise ValueError(f"expected one .xplane.pb under {log_dir}, found "
                         f"{len(files)}")
    device_ops, spans = read_xplane(files[0], n_devices)
    if not device_ops:
        raise ValueError(f"{files[0]}: no TPU device plane")
    return reduce_events(device_ops, spans)
