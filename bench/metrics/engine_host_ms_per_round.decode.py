"""Device idle under the engine's phase spans (engine.*) per traced round of the decode serving cell, in ms."""

from bench.harness import readers


def read(rec):
    return readers.engine_host_ms_per_round(rec)
