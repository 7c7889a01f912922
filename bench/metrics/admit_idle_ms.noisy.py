"""admit_idle_ms, in the noisy-neighbour cell, where the device's idle
time under these regions is throughput lost. The same reading as
admit_idle_ms, which moves time to first token in the chat cell."""
from bench.metrics.admit_idle_ms import read  # noqa: F401
