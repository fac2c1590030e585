"""Host milliseconds per inner round spent copying the parameters and the
Adam state before the steps donate them: the program's ``round_copy``
span (per-leaf ``jnp.copy``) per ``worker_round``."""
from chipbench.per_round import ms_per_round


def read(run):
    return ms_per_round(run, "round_copy")
