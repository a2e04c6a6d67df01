"""The stencil kernel's share of its roofline, in %: the least time the
chip needs for the sweeps whose kernel ran whole inside the trace (bytes of
``work/stencil.py`` over the HBM peak; no float32 vector peak is published,
so the bytes alone bound it), over the device time of those kernel calls
(the trace's Mosaic custom calls)."""
from bench.work import stencil as work


def read(run):
    t = run.trace
    if t is None or not t.kernel_calls or "hbm_bytes_per_s" not in run.peaks:
        return None
    least = t.kernel_calls * work.roofline_s(run.spec.config, run.peaks)
    return 100.0 * least / t.kernel_seconds
