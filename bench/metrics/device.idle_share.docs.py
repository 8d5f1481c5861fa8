"""Share of the traced window in which no operation ran on the device
(mean over the chips), in percent."""

from bench.metrics._common import idle_share


def read(record):
    return idle_share(record)
