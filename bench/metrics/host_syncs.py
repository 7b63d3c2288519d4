"""Per-layer metric ``host_syncs``: the simulator's blocking device -> host
reads (sim/sync) per round.
"""
from benchkit import readers

HOST_SPANS = ("sim/sync",)


def read(ctx):
    return readers.host_span_count(ctx, HOST_SPANS)
