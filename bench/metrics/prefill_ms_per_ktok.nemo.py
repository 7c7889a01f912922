"""Device milliseconds of the prefill programs per 1000 prompt tokens, in
the Nemotron-H decode-heavy cell, where each admission's prefill takes
engine time from decoding. The same reading as prefill_ms_per_ktok."""
from bench.metrics.prefill_ms_per_ktok import read  # noqa: F401
