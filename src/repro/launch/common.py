"""Shared CLI surface for the launch entry points.

``mine``, ``recommend`` and ``stream`` drive the same substrate (corpus
generation, the heterogeneity profile, the switching policy, the kernel
data plane), so the flags that select it are declared once here and
attached by each entry point.  This is what keeps the CLIs from drifting:
``recommend`` once hardcoded its ``--policy`` choices and silently fell
behind ``POLICY_NAMES`` — a flag added here shows up everywhere with the
same name, default and help text.

Each ``add_*`` helper attaches one coherent flag group to an existing
parser; ``standard_parser()`` builds a parser with all of them for the
entry points that want the full set.  ``enable_compile_cache()`` is the
one place the entry points (and ``chip_smoke.py``) turn on JAX's
persistent compilation cache.
"""
from __future__ import annotations

import argparse
import os

from repro.core.hetero import HeterogeneityProfile
from repro.runtime import POLICY_NAMES

# Named core profiles every CLI's --profile resolves through (paper §IV:
# one fast core + progressively slower ones; the alternatives isolate the
# scheduler's contribution).
PROFILES = {
    "paper": HeterogeneityProfile.paper,
    "homogeneous": lambda: HeterogeneityProfile.homogeneous(4, 200.0),
    "straggler": lambda: HeterogeneityProfile.straggler(8, 2, 4.0),
}


# The cache directory when JAX_COMPILATION_CACHE_DIR is unset: fixed, at
# the root of the checkout (src/repro/launch/ -> three levels up), never
# per run — a directory that moves never hits.
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache; returns its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here.  Otherwise the cache goes to
    ``CHECKOUT_CACHE_DIR``.  Call before the first compilation."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR


def add_corpus_args(ap: argparse.ArgumentParser, n_tx: int = 8192,
                    n_items: int = 128, min_support: float = 0.02,
                    min_confidence: float = 0.6) -> argparse.ArgumentParser:
    """Synthetic-corpus shape and mining thresholds."""
    ap.add_argument("--n-tx", type=int, default=n_tx)
    ap.add_argument("--n-items", type=int, default=n_items)
    ap.add_argument("--min-support", type=float, default=min_support)
    ap.add_argument("--min-confidence", type=float, default=min_confidence)
    return ap


def add_runtime_args(ap: argparse.ArgumentParser,
                     policy: str = "static",
                     split: str = "lpt") -> argparse.ArgumentParser:
    """Heterogeneity profile + switching policy + tile split."""
    ap.add_argument("--profile", default="paper", choices=sorted(PROFILES))
    ap.add_argument("--policy", default=policy, choices=list(POLICY_NAMES),
                    help="switching policy: plan once (static), closed-loop "
                         "EWMA + speculation (dynamic), roofline-seeded "
                         "costs (costmodel)")
    ap.add_argument("--split", default=split,
                    choices=["lpt", "proportional", "equal"],
                    help="tile split strategy across the core profile")
    return ap


def add_dataplane_args(ap: argparse.ArgumentParser) -> argparse.ArgumentParser:
    """Kernel backend selection + autotune winner cache."""
    ap.add_argument("--data-plane", default="auto",
                    choices=["auto", "pallas", "ref"])
    ap.add_argument("--autotune", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="use the checked-in kernel winner cache for "
                         "variant/tile selection (--no-autotune = "
                         "roofline-seeded defaults)")
    return ap


def add_seed_arg(ap: argparse.ArgumentParser) -> argparse.ArgumentParser:
    ap.add_argument("--seed", type=int, default=0)
    return ap


def standard_parser(**corpus_defaults) -> argparse.ArgumentParser:
    """Parser with the full shared flag set (corpus, runtime, data plane,
    seed); entry points add their own flags on top."""
    ap = argparse.ArgumentParser()
    add_corpus_args(ap, **corpus_defaults)
    add_runtime_args(ap)
    add_dataplane_args(ap)
    add_seed_arg(ap)
    return ap
