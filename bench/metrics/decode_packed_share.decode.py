"""Share of decode rounds in the window that took the packed kernel path."""

from bench.harness import readers


def read(rec):
    return readers.decode_packed_share(rec)
