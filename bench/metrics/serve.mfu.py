"""Model FLOPs of every prompt and output token processed in the window
(``work/decoder.py``), over the window and the chip's bfloat16 peak, in %:
the whole serving step's share of the peak, kernels and host work alike."""


def read(run):
    peak = run.peaks.get("bf16_flops_per_s")
    flops = run.cell.window_flops()
    if not peak or not flops:
        return None
    return 100.0 * flops / ((run.cell.t_close - run.cell.t_open) * peak)
