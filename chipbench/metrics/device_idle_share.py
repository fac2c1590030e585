"""Share of the traced window in which no operation ran on the chip,
averaged over the cell's chips."""


def read(run):
    busy = run.busy_s()
    return None if busy is None else 100.0 * (1.0 - busy / run.window_s)
