"""Share of the roofline reached by the decode program, in the saturated
cell, where the decode sets the throughput. The same reading as
decode_roofline, which moves the inter-token tail below the knee."""
from bench.metrics.decode_roofline import read  # noqa: F401
