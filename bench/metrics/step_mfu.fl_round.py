"""Per-layer metric ``step_mfu.fl_round``: model FLOPs of the FL round's
local steps over the traced round time, chips and bf16 peak.
"""
from benchkit.readers import step_mfu as read  # noqa: F401
