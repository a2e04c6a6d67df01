"""Mean number of host syncs (waits on or fetches of a device value) the
scheduler makes in one tick, over the window's ticks: the "host_syncs" that
each "step" span carries."""


def read(run):
    ticks = run.cell.window_stats("step")
    if not ticks:
        return None
    return sum(s.extra["host_syncs"] for s in ticks) / len(ticks)
