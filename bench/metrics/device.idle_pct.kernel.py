"""Share of the traced window in which no op ran on the device, in %:
1 - (union of the device op intervals) / (traced window)."""


def read(run):
    return None if run.trace is None else run.trace.idle_pct
