"""Device milliseconds per inner step: the jitted step program's device
time in the window over its executions (forward, backward and AdamW)."""


def read(run):
    seconds, n = run.module_seconds("inner_step")
    return 1e3 * seconds / n if n else None
