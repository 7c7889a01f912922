"""Share of the traced window in which the device is idle, in the
decode-heavy cell, where idle time is throughput lost. The same reading
as device_idle_pct."""
from bench.metrics.device_idle_pct import read  # noqa: F401
