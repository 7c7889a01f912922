"""Device milliseconds per decode program under the program's ``attn``
name scope, in the Nemotron-H decode-heavy cell: its three attention
blocks (projections, the K/V row write, the flash-decode kernel with 16
query heads a KV head on a TPU, the out-projection). The same reading as
attn_ms."""
from bench.metrics.attn_ms import read  # noqa: F401
