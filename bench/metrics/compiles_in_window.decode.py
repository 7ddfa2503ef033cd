"""Programs compiled or loaded inside the window of the decode serving cell."""

from bench.harness import readers


def read(rec):
    return readers.compiles(rec)
