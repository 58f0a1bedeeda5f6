"""`record_ratio` as a percentage: 100 x the ratio of two of the
records' fields, each summed over the groups of the window.

params: as `record_ratio` (``field``, ``per``, ``once_per``).  None
where `record_ratio` gives None (a program without the counter)."""

from benchmark.readers import record_ratio


def read(run, params):
    ratio = record_ratio.read(run, params)
    return None if ratio is None else 100.0 * ratio
