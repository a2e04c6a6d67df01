"""Mean host-clock time of the scheduler's per-row sampling in each decode
tick of the window: its "sample" spans, from after the KV-cache write-back
to the last token appended (at pipeline depth 0, one argmax and one host
fetch per row)."""


def read(run):
    spans = run.cell.window_stats("sample")
    if not spans:
        return None
    return 1e3 * sum(s.seconds for s in spans) / len(spans)
