"""Mean host-clock time of the scheduler's decode steps in the window (the
"decode" StepStats ``ServeScheduler`` records after ``block_until_ready``)."""


def read(run):
    steps = run.cell.window_stats("decode")
    if not steps:
        return None
    return 1e3 * sum(s.seconds for s in steps) / len(steps)
