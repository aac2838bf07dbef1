"""device_idle.train: per cent of the traced training window with no
operation on the device (profiler trace)."""
from bench.readers import idle_share as read  # noqa: F401
