"""Device milliseconds per decode program under ``attn``, in the saturated
cell. The same reading as attn_ms."""
from bench.metrics.attn_ms import read  # noqa: F401
