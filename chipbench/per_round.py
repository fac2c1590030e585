"""Host time per inner round of one program span: the sum of the span's
durations in the window over the window's ``worker_round`` spans. The
spans inside the round are thus on the scale of ``round_host_ms`` and
nearly add up to it. A span the program does not record reads nothing."""


def rounds(run) -> int:
    """The window's ``worker_round`` spans."""
    return sum(1 for s in run.spans if s[0] == "worker_round")


def ms_per_round(run, name: str):
    n, ms = rounds(run), run.span_ms(name)
    return sum(ms) / n if n and ms else None
