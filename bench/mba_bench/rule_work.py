"""Operations and bytes of one rule_match scoring step, for its roofline
share.

The work is that of the problem the step solves, not of the padded
launch, so the share reads the same whichever variant runs: B baskets
that missed the result cache, scored against the R true rows of the rule
index, whose antecedents span I_eff distinct items (an item that is only
ever a consequent takes no part in the containment test):

    ops   = 2 · B · I_eff · R              (int8 multiply-adds of Q · Aᵀ)
    bytes = B·I_eff/8 + R·I_eff/8 + 4R     (both bitmaps as bits, the
                                            confidences)

The least time of a step is ``work.least_seconds``'s: the larger of ops
over the int8 peak and bytes over HBM bandwidth.
"""
from __future__ import annotations

from typing import Tuple

import numpy as np


def rule_match_work(b: int, r: int, i_eff: int) -> Tuple[float, float]:
    ops = 2.0 * b * i_eff * r
    nbytes = b * i_eff / 8.0 + r * i_eff / 8.0 + 4.0 * r
    return ops, nbytes


def antecedent_items(index) -> int:
    """Distinct items among the antecedents of a ``RuleIndex``'s true
    rows."""
    return int(np.asarray(index.ante[:index.n_rows]).any(axis=0).sum())
