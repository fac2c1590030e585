"""Host milliseconds per inner round spent forming the pseudo-gradient:
the program's ``pseudo_gradient`` span (theta_bar - theta_H, leaf by
leaf) per ``worker_round``."""
from chipbench.per_round import ms_per_round


def read(run):
    return ms_per_round(run, "pseudo_gradient")
