"""Share of the roofline reached by the decode program, in the Nemotron-H
decode-heavy cell. The same reading as decode_roofline; the operations
and bytes are the Mamba-2, expert and attention architecture's
(bench/arch/mamba_moe.py ``decode_call``: every live slot's f32 state
read and written in each mixer)."""
from bench.metrics.decode_roofline import read  # noqa: F401
