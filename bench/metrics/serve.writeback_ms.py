"""Mean host-clock time of the scheduler's KV-cache write-back in each
decode tick of the window: its "writeback" spans, around the scatter of the
step's cache into the slot pool (dispatch time: the device work it queues
is waited for by the next host sync)."""


def read(run):
    spans = run.cell.window_stats("writeback")
    if not spans:
        return None
    return 1e3 * sum(s.seconds for s in spans) / len(spans)
