"""Least time of the traced steps' packed forward attention over its kernel's device time
(the forward recomputed under remat counts as kernel time, not as work)."""

from bench.harness import readers
from bench.harness import work


def read(rec):
    return readers.packed_train_roofline(rec, "packed_fwd", work.packed_fwd)
