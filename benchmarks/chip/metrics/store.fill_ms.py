"""Expert store (serving/offload.py SlotBuffer.fill): median time of one
expert's fill, the store's fetch, the host-to-device copy and the
``_write_slot`` dispatch, from the program's ``store.fill`` spans on the
profiler's clock."""
from benchmarks.chip.spans import median_ms


def read(ctx):
    return median_ms(ctx, "store.fill")
