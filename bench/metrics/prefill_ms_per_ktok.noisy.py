"""Device milliseconds of the prefill programs (jit__prefill) per 1000
prompt tokens prefilled, over the steps of the traced window: where the
heavy tenants' 1536-token prompts take engine time from decoding. The
same reading as prefill_ms_per_ktok, which moves time to first token in
the chat cells; here it moves the throughput."""
from bench.metrics.prefill_ms_per_ktok import read  # noqa: F401
