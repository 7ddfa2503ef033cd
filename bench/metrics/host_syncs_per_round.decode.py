"""Blocking device-to-host reads (engine.to_host spans) per traced round of the decode serving cell."""

from bench.harness import readers


def read(rec):
    return readers.host_syncs_per_round(rec)
