"""Required decode FLOPs over device-busy time in engine.decode_round at the bf16 peak."""

from bench.harness import readers


def read(rec):
    return readers.decode_mfu(rec)
