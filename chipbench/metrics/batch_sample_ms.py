"""Host milliseconds per inner round spent drawing the H batches: the
program's ``batch_sample`` spans around ``ShardSampler.sample`` (the NumPy
sampler and its loop over token positions) per ``worker_round``."""
from chipbench.per_round import ms_per_round


def read(run):
    return ms_per_round(run, "batch_sample")
