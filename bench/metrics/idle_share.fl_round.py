"""Per-layer metric ``idle_share.fl_round``: share of the traced window in
which no op ran on the device.
"""
from benchkit.readers import idle_share as read  # noqa: F401
