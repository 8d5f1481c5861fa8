"""Seconds from process start to the opening of the window: weights,
compilation or loading from the cache, warm-up and the lead-in."""


def read(record):
    return record.get("setup_s")
