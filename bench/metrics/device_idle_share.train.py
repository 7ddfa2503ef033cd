"""Share of the traced window in which no operation ran on the device."""

from bench.harness import readers


def read(rec):
    return readers.idle_share(rec)
