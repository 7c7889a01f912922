"""Host milliseconds per ServeEngine.step, in the saturated cell, where
every slot is busy and the throughput is the slots over the step. The
same reading as step_ms, which moves the inter-token tail below the
knee."""
from bench.metrics.step_ms import read  # noqa: F401
