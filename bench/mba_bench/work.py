"""Operations and bytes of a kernel's problem, for its roofline share.

The work is that of the problem the kernel solves, not of the padded
shapes it launched, so the share reads the same whichever variant runs:

support_count, one Apriori round over n transactions and m candidates
whose items span I_eff distinct items:
    ops   = 2 · n · I_eff · m            (int8 multiply-adds of T · Cᵀ)
    bytes = n·I_eff/8 + m·I_eff/8 + 4m   (both bitmaps as bits, the counts)

The least time of a call is the larger of ops over the int8 peak and
bytes over HBM bandwidth.  A formulation that does less work (transaction
trimming, the vertical Eclat rounds) needs these counts corrected in the
benchmark before its share means anything.
"""
from __future__ import annotations

from typing import Dict, Iterable, Tuple


def support_count_work(n_tx: int, m: int, i_eff: int) -> Tuple[float, float]:
    ops = 2.0 * n_tx * i_eff * m
    nbytes = n_tx * i_eff / 8.0 + m * i_eff / 8.0 + 4.0 * m
    return ops, nbytes


def least_seconds(calls: Iterable[Tuple[float, float]],
                  peak: Dict[str, float]) -> float:
    """Sum over calls of max(ops / int8 peak, bytes / HBM bandwidth)."""
    return sum(max(ops / peak["int8_ops_per_s"],
                   nbytes / peak["hbm_bytes_per_s"]) for ops, nbytes in calls)


def roofline_share(least_s: float, kernel_s: float):
    """Percent of the roofline; None where the kernel was not seen."""
    if kernel_s <= 0 or least_s <= 0:
        return None
    return 100.0 * least_s / kernel_s
