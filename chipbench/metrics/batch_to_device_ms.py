"""Host milliseconds per inner round spent handing the H batches to the
device: the program's ``batch_to_device`` spans around ``jnp.asarray`` of
each batch per ``worker_round``."""
from chipbench.per_round import ms_per_round


def read(run):
    return ms_per_round(run, "batch_to_device")
