"""The whole step's share of the chip's peak bf16 FLOP/s, in the
decode-heavy cell. The same reading as mfu_pct, with the latent-attention
and expert architecture's counts (bench/arch/mla_moe.py)."""
from bench.metrics.mfu_pct import read  # noqa: F401
