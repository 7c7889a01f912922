"""Host milliseconds per ServeEngine.step, in the decode-heavy cell,
where the throughput is the busy slots over the step. The same reading
as step_ms."""
from bench.metrics.step_ms import read  # noqa: F401
