"""The share of cache blocks the decode's attention read, in the
saturated cell, where it moves the throughput. The same reading as
decode_kv_read_pct."""
from bench.metrics.decode_kv_read_pct import read  # noqa: F401
