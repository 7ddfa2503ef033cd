"""Required FLOPs of the train steps completed in the traced window over the window at the bf16 peak."""

from bench.harness import readers


def read(rec):
    return readers.train_step_mfu(rec)
