"""Per-layer metric ``host_prep_ms``: host time per round of the
simulator's input preparation: the compiled chunk draw (sim/chunk_inputs)
or, for a ragged store, the per-draw path (sim/round_inputs, sim/stack).
"""
from benchkit import readers

HOST_SPANS = ("sim/chunk_inputs", "sim/round_inputs", "sim/stack")


def read(ctx):
    return readers.host_span_ms(ctx, HOST_SPANS)
