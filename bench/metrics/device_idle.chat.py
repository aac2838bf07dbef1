"""device_idle.chat: per cent of the traced chat window with no
operation on the device (profiler trace)."""
from bench.readers import idle_share as read  # noqa: F401
