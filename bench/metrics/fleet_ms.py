"""Per-layer metric ``fleet_ms``: device time per round of the ops under
the fleet/* spans.
"""
from benchkit.readers import fleet_ms as read  # noqa: F401
