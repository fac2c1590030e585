"""Device milliseconds of the server's commit programs per arrival (the
packing of the delta, the HeLoCo statistics and the fused correct and
outer-Nesterov sweep, or the fused K-flush)."""


def read(run):
    seconds, n = run.module_seconds("commit")
    arrivals = sum(run.arrivals())
    return 1e3 * seconds / arrivals if n and arrivals else None
