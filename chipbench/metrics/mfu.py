"""Model FLOP/s utilisation of the whole step: the forward and backward
matmul operations of the tokens committed in the traced window (the
cell architecture's ``train_flops_per_token``) over window x chips x
bf16 peak."""


def read(run):
    if not run.tokens:
        return None
    work = run.tokens * run.cell.arch.train_flops_per_token(
        run.cell.model, run.cell.job["seq_len"])
    return 100.0 * work / (run.window_s * run.n_chips
                           * run.peaks["bf16_flops_per_s"])
