"""Device milliseconds per execution of the decode program, in the
saturated cell, where it sets the throughput. The same reading as
decode_ms, which moves the inter-token tail below the knee."""
from bench.metrics.decode_ms import read  # noqa: F401
