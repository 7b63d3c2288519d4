"""Per-layer metric ``step_mfu.fleet_scan``: QNN training FLOPs per round
over the traced round time and bf16 peak.
"""
from benchkit.readers import step_mfu as read  # noqa: F401
