"""The whole step's share of the chip's peak bf16 FLOP/s, in the
Nemotron-H decode-heavy cell. The same reading as mfu_pct, with the
Mamba-2, expert and attention architecture's counts
(bench/arch/mamba_moe.py)."""
from bench.metrics.mfu_pct import read  # noqa: F401
