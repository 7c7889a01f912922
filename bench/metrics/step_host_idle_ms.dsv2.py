"""step_host_idle_ms, in the decode-heavy cell, where every slot decodes
and the device's idle time under these regions is throughput lost. The
same reading as step_host_idle_ms."""
from bench.metrics.step_host_idle_ms import read  # noqa: F401
