"""Host milliseconds per inner round spent dispatching the H jitted inner
steps: the program's ``inner_dispatch`` spans around ``step_fn(params, opt,
batch)`` per ``worker_round`` (host clock; the device runs them
asynchronously)."""
from chipbench.per_round import ms_per_round


def read(run):
    return ms_per_round(run, "inner_dispatch")
