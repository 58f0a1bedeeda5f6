"""Time in collective operations while no other operation runs on that
chip, over the traced window (mean over chips).  Nothing to read on a
trace that holds no collective."""


def read(run, params):
    t = run["trace"]
    if not t["chips"] or t["collective_s"] <= 0 or run["window_s"] <= 0:
        return None
    return 100.0 * t["collective_exposed_s"] / run["window_s"]
