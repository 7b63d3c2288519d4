"""Per-layer metric ``wire_ms``: device time per round of the ops under the
wire/* spans and fl/apply.
"""
from benchkit.readers import wire_ms as read  # noqa: F401
