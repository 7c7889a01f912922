"""Share of the roofline reached by the decode program, in the
decode-heavy cell. The same reading as decode_roofline; the operations
and bytes are the latent-attention and expert architecture's
(bench/arch/mla_moe.py ``decode_call``)."""
from bench.metrics.decode_roofline import read  # noqa: F401
