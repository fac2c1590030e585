"""Host milliseconds per inner round spent capturing a round at dispatch:
the program's ``round_dispatch`` spans (the server's look-ahead
``worker_init``, the per-leaf copy of the model and the task snapshot)
per ``worker_round``; outside ``worker_round``."""
from chipbench.per_round import ms_per_round


def read(run):
    return ms_per_round(run, "round_dispatch")
