"""Host milliseconds per RateController.tick, in the Nemotron-H
decode-heavy cell: a tick holds up the step that runs it, so it moves the
throughput there. The same reading as control_tick_ms."""
from bench.metrics.control_tick_ms import read  # noqa: F401
