"""device_idle.longprompt: per cent of the traced long-prompt window with no
operation on the device (profiler trace)."""
from bench.readers import idle_share as read  # noqa: F401
