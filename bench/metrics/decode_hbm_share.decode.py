"""Required decode bytes (weights, valid KV) over device-busy time in engine.decode_round at peak HBM bandwidth."""

from bench.harness import readers


def read(rec):
    return readers.decode_hbm_share(rec)
