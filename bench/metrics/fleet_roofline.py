"""Per-layer metric ``fleet_roofline``: reading and writing the fleet state
once at HBM peak, over the fleet's device time.
"""
from benchkit.readers import fleet_roofline as read  # noqa: F401
