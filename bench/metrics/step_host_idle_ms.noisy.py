"""step_host_idle_ms, in the noisy-neighbour cell, where the device's idle
time under these regions is throughput lost. The same reading as
step_host_idle_ms, which moves the inter-token tail below the knee."""
from bench.metrics.step_host_idle_ms import read  # noqa: F401
