"""Least time of the traced steps' packed attention backward (dq and dkv kernels
together) over their device time."""

from bench.harness import readers
from bench.harness import work


def read(rec):
    return readers.packed_train_roofline(rec, "packed_bwd", work.packed_bwd)
