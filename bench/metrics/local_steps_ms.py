"""Per-layer metric ``local_steps_ms``: device time per round of the ops
under the fl/local_steps span.
"""
from benchkit.readers import local_steps_ms as read  # noqa: F401
