"""Share of the HBM roofline that the packed commit kernels reach: the
bytes they must move for the window's commits (``flops.commit_bytes``)
over their device time at the chip's peak bandwidth."""
from chipbench import flops


def read(run):
    seconds, n = run.kernel_seconds("commit_kernels")
    commits = run.arrivals()
    if not n or not commits:
        return None
    rows = flops.packed_rows(run.cell.arch, run.cell.model)
    need = sum(flops.commit_bytes(rows, k) for k in commits)
    return 100.0 * need / (seconds * run.peaks["hbm_bytes_per_s"])
