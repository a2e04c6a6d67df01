"""The Gated DeltaNet decode kernel's share of its roofline, in %: the least
time the chip needs for the ``gdn_decode`` calls that ran whole inside the
trace (``work/qwen3_next.gdn_decode_bytes`` at the pool's rows over the HBM
peak; the kernel is bound by its state's bytes), over those calls' device
time.  The reduced trace counts every Mosaic kernel in ``kernel_calls`` and
``kernel_seconds``; they are this kernel's only if its own op time matches
them, up to the calls that the window's two ends cut, so anything else
reads None."""
from bench.work import qwen3_next as work

OP = "custom-call gdn_decode"


def read(run):
    t = run.trace
    if t is None or not t.kernel_calls or "hbm_bytes_per_s" not in run.peaks:
        return None
    op = t.op_seconds.get(OP)
    if op is None or not -1e-9 <= op - t.kernel_seconds \
            <= 2.0 * t.kernel_seconds / t.kernel_calls:
        return None
    cfg = run.spec.config
    least = t.kernel_calls * work.gdn_decode_bytes(
        cfg, cfg["serving"]["slots"]) / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / t.kernel_seconds
