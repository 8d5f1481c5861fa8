"""95th percentile over every gap between consecutive output tokens of
every request whose later token arrived in the window."""

from bench.metrics._common import percentile


def read(record):
    p = percentile(record["itl_s"], 95)
    return None if p is None else p * 1e3
