"""The whole step's share of the chip's peak bf16 FLOP/s, in the
saturated cell, where it bounds the throughput. The same reading as
mfu_pct, which moves the inter-token tail below the knee."""
from bench.metrics.mfu_pct import read  # noqa: F401
