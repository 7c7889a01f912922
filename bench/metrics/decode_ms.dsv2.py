"""Device milliseconds per execution of the decode program, in the
decode-heavy cell, where every slot decodes and the step sets the
throughput. The same reading as decode_ms."""
from bench.metrics.decode_ms import read  # noqa: F401
