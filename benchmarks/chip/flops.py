"""What the operation and byte counts of every kind share.

Each kind of served architecture counts its own work
(``archs/<kind>.py``: ``prefill_flops``, ``decode_flops`` and
``decode_attention_cost``, the last over the layers that attend), from the
sizes in a configuration file's ``architectures`` entry, never the
program's own config objects. What is counted is what the mathematics
needs, whatever implements it: a decode step attends over the ``kv_len``
positions a row holds, not over the ``max_seq`` buffer it is handed, and a
prefill counts the prompt's tokens, not the bucket it is padded to.
"""
from __future__ import annotations

from typing import Dict

BF16 = 2   # bytes per element of the served weights and caches


def roofline_seconds(flops: float, nbytes: float, peak: Dict) -> float:
    """The least time the chip could take: the larger of the compute bound
    and the memory bound."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])
