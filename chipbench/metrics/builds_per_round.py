"""Programs lowered per inner round: the program's zero-length
``program_build`` spans (one per ``jaxpr_to_mlir_module`` event, whether the
persistent cache then hits or not) over the window's ``worker_round``
spans. The counter came with the spans inside the round, so a program
without ``inner_dispatch`` spans reads nothing rather than 0."""
from chipbench.per_round import rounds


def read(run):
    n = rounds(run)
    if not n or not run.span_ms("inner_dispatch"):
        return None
    return len(run.span_ms("program_build")) / n
