"""The one traffic generator.  A mix is a JSON file under ``traffic/``;
its ``kind`` picks the loop that drives the system, and the rest are
parameters:

``mine_loop``   closed loop: mines run back to back over the corpus, one
                job at a time.  No parameters.
``open_loop``   independent shoppers: requests sent on a schedule fixed in
                advance, whatever the server does.
    rate_qps       mean offered rate of Poisson arrivals
    baskets        "quest": fresh transactions of the configuration's
                   generator

A window of ``seconds`` always holds ``round(rate_qps * seconds)``
requests, so every seed offers the same work in another order: the
arrivals are that many uniform instants, a Poisson process given its
count.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

KINDS = ("mine_loop", "open_loop")


def validate(spec: Dict) -> Dict:
    kind = spec.get("kind")
    if kind not in KINDS:
        raise ValueError(f"traffic kind {kind!r} is not one of {KINDS}")
    if kind == "open_loop":
        if float(spec.get("rate_qps", 0)) <= 0:
            raise ValueError("open_loop traffic needs rate_qps > 0")
        if spec.get("arrivals", "poisson") != "poisson":
            raise ValueError(f"unknown arrivals {spec['arrivals']!r}")
        if spec.get("baskets", "quest") != "quest":
            raise ValueError(f"unknown baskets {spec['baskets']!r}")
    return spec


def n_requests(spec: Dict, seconds: float) -> int:
    return max(1, int(round(float(spec["rate_qps"]) * seconds)))


def arrival_offsets(spec: Dict, n: int, seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """n sorted send instants in [0, seconds)."""
    return np.sort(rng.uniform(0.0, seconds, n))


def baskets(spec: Dict, model, n: int, rng: np.random.Generator) -> np.ndarray:
    """n basket bitmaps [n, N] for an open-loop mix."""
    return model.baskets(n, rng)
