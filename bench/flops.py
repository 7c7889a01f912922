"""The roofline: the least time the chip could take for a call.

The operations and bytes of a call are the architecture's own, from
shapes alone (``bench/arch/<arch>.py``: ``decode_call``, ``prefill_flops``);
the chip's peaks are in ``bench/peaks.json``.
"""
from __future__ import annotations

from typing import Dict


def roofline_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The least time the chip could take: the larger of the compute and
    the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
