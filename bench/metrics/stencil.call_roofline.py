"""The whole sweep's share of the stencil's roofline, in %: the least time
a sweep needs (``work/stencil.py`` over the HBM peak) over call_ms taken on
the host clock in the part of the window before the trace began.  Work
outside the kernel (the pad copy, the halo written back) counts against it,
so it bounds any claim on call_ms."""
from bench.work import stencil as work


def read(run):
    ms = run.cell.untraced_call_ms()
    if ms is None or "hbm_bytes_per_s" not in run.peaks:
        return None
    return 100.0 * work.roofline_s(run.spec.config, run.peaks) / (ms * 1e-3)
