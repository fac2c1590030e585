"""Host milliseconds per eval: the mean of the program's ``eval`` spans
in the window. Each eval ends in one float() per language, so the span
covers the device work it waits for."""


def read(run):
    ms = run.span_ms("eval")
    return sum(ms) / len(ms) if ms else None
