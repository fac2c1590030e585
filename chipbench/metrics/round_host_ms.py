"""Host milliseconds per inner round: the mean of the program's
``worker_round`` spans in the window (batch sampling, dispatch of the H
inner steps and the pseudo-gradient; host clock, no device sync)."""


def read(run):
    ms = run.span_ms("worker_round")
    return sum(ms) / len(ms) if ms else None
