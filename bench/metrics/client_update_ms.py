"""Per-layer metric ``client_update_ms``: device time per round of the ops
under the simulator's sim/client_update span (the K clients' vmapped local
steps, deltas and quantization).
"""
from benchkit import readers

SPANS = ("sim/client_update",)


def read(ctx):
    return readers.span_in_ms(ctx, SPANS)
