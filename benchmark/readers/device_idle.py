"""Device idle share of the traced window: 1 - the union of operation
intervals (mean over the cell's chips) over the window."""


def read(run, params):
    if not run["trace"]["chips"] or run["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - run["trace"]["busy_s"] / run["window_s"])
