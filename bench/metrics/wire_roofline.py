"""Per-layer metric ``wire_roofline``: least time of the aggregation's
interface bytes over its device time.
"""
from benchkit.readers import wire_roofline as read  # noqa: F401
