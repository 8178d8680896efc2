"""The autotune winner cache — ``(kernel, shape-bucket, device kind)`` →
measured best config.

Key scheme
----------
``kernel|bucket|device``, e.g. ``support_count|n256_m2048_i128|cpu``:

* *kernel* — ``support_count`` | ``rule_match`` (the tunable hot loops).
* *bucket* — every (padded) call shape rounded up per-dimension to the
  next power of two, so the cache stays O(log) in each axis while the
  planes' pad-to-bucket shape discipline keeps real calls near their
  bucket corner.
* *device* — ``jax.devices()[0].device_kind`` (spaces → ``_``): tile
  winners are a per-silicon property, so a cache tuned on one device
  kind never silently configures another — lookups for an unknown
  device fall through to the roofline-seeded defaults.

Entries store the exact shape they were tuned at, the winning config,
its measured cost, and the full sweep (for audit + the argmin property
test).  ``lookup`` falls back to the *nearest* cached bucket (log-scale
distance, deterministic tie-break) for the same kernel+device before
giving up — a lattice sweep then covers every in-between shape.

Degradation contract: a missing or corrupt cache file loads as an empty
cache (the parse error is kept on ``load_error``, never raised), and an
empty lookup returns ``None`` — callers then use
:func:`repro.launch.tuning.default_config`, the roofline-seeded default.
"""
from __future__ import annotations

import json
import os
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

DEFAULT_CACHE_PATH = os.path.join(os.path.dirname(__file__), "cache.json")

_DIM_NAMES = {
    "support_count": ("n", "m", "i"),
    "intersect_count": ("m", "w"),
    "rule_match": ("b", "r", "i"),
}


def device_kind() -> str:
    """Canonical device-kind token for cache keys (lazy jax import so the
    cache file itself can be read without a backend)."""
    import jax
    return jax.devices()[0].device_kind.replace(" ", "_")


def _pow2_ceil(x: int) -> int:
    return 1 << max(int(x) - 1, 0).bit_length() if x > 1 else 1


def shape_bucket(kernel: str, shape: Tuple[int, ...]) -> str:
    names = _DIM_NAMES.get(kernel)
    if names is None or len(shape) != len(names):
        raise ValueError(f"unknown kernel/shape: {kernel} {shape}")
    return "_".join(f"{n}{_pow2_ceil(d)}" for n, d in zip(names, shape))


def _bucket_dims(bucket: str) -> List[int]:
    return [int(part[1:]) for part in bucket.split("_")]


@dataclass
class AutotuneCache:
    """In-memory view of one cache file (see module docstring)."""

    entries: Dict[str, Dict[str, Any]] = field(default_factory=dict)
    path: Optional[str] = None
    load_error: Optional[str] = None

    # ------------------------------------------------------------------
    @classmethod
    def load(cls, path: str = DEFAULT_CACHE_PATH) -> "AutotuneCache":
        """Read a cache file; missing/corrupt files load empty, with the
        reason on ``load_error`` — autotuning must never take a plane
        down, it can only make it faster."""
        try:
            with open(path) as f:
                data = json.load(f)
            entries = data["entries"]
            if not isinstance(entries, dict):
                raise TypeError("entries must be an object")
            for key, ent in entries.items():
                if "config" not in ent or "cost_us" not in ent:
                    raise KeyError(f"entry {key!r} missing config/cost_us")
            return cls(entries=dict(entries), path=path)
        except FileNotFoundError as e:
            return cls(path=path, load_error=str(e))
        except (json.JSONDecodeError, KeyError, TypeError, ValueError) as e:
            return cls(path=path, load_error=f"corrupt cache {path}: {e}")

    def save(self, path: Optional[str] = None) -> str:
        path = path or self.path or DEFAULT_CACHE_PATH
        payload = {
            "meta": {
                "note": "autotuned kernel configs; key = "
                        "kernel|shape-bucket|device_kind",
                "refresh": "python -m repro.launch.autotune",
            },
            "entries": {k: self.entries[k] for k in sorted(self.entries)},
        }
        with open(path, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
            f.write("\n")
        self.path = path
        return path

    # ------------------------------------------------------------------
    @staticmethod
    def key(kernel: str, shape: Tuple[int, ...],
            device: Optional[str] = None) -> str:
        return f"{kernel}|{shape_bucket(kernel, shape)}|" \
               f"{device or device_kind()}"

    def put(self, kernel: str, shape: Tuple[int, ...],
            config: Dict[str, Any], cost_us: float,
            swept: Optional[List[Dict[str, Any]]] = None,
            device: Optional[str] = None) -> str:
        key = self.key(kernel, shape, device)
        self.entries[key] = {
            "shape": [int(d) for d in shape],
            "config": dict(config),
            "cost_us": round(float(cost_us), 3),
            "source": "measured",
            "swept": swept or [],
        }
        return key

    # ------------------------------------------------------------------
    def lookup(self, kernel: str, shape: Tuple[int, ...],
               device: Optional[str] = None) -> Optional[Dict[str, Any]]:
        """Best known entry for this call shape: exact bucket, else the
        nearest cached bucket (same kernel+device) by log2 distance."""
        device = device or device_kind()
        exact = self.entries.get(self.key(kernel, shape, device))
        if exact is not None:
            return exact
        want = _bucket_dims(shape_bucket(kernel, shape))
        prefix, suffix = f"{kernel}|", f"|{device}"
        best_key, best_dist = None, None
        for key in sorted(self.entries):
            if not (key.startswith(prefix) and key.endswith(suffix)):
                continue
            dims = _bucket_dims(key.split("|")[1])
            dist = sum(abs(a.bit_length() - b.bit_length())
                       for a, b in zip(dims, want))
            if best_dist is None or dist < best_dist:
                best_key, best_dist = key, dist
        return self.entries.get(best_key) if best_key else None

    def entries_for(self, kernel: str, device: Optional[str] = None
                    ) -> List[Dict[str, Any]]:
        device = device or device_kind()
        prefix, suffix = f"{kernel}|", f"|{device}"
        return [self.entries[k] for k in sorted(self.entries)
                if k.startswith(prefix) and k.endswith(suffix)]

    def has_kernel(self, kernel: str, device: Optional[str] = None) -> bool:
        return bool(self.entries_for(kernel, device))

    def __len__(self) -> int:
        return len(self.entries)


# ---------------------------------------------------------------------------
# module-level default (the checked-in cache) + the ops-facing resolver
# ---------------------------------------------------------------------------

_default: Optional[AutotuneCache] = None


def default_cache(reload: bool = False) -> AutotuneCache:
    global _default
    if _default is None or reload:
        _default = AutotuneCache.load(DEFAULT_CACHE_PATH)
    return _default


def _resolve(kernel: str, shape: Tuple[int, ...], tuning: Any,
             device: Optional[str] = None) -> Tuple[Dict[str, Any], str]:
    """(config, source) with source ``pinned`` | ``cache`` | ``roofline``."""
    from repro.launch.tuning import default_config, fit_config
    if isinstance(tuning, dict):
        return dict(tuning), "pinned"
    if tuning is not False:
        cache = tuning if isinstance(tuning, AutotuneCache) else default_cache()
        entry = cache.lookup(kernel, shape, device)
        cfg = None if entry is None else fit_config(kernel, shape,
                                                    entry["config"])
        if cfg is not None:
            return cfg, "cache"
    return default_config(kernel, shape), "roofline"


def resolve_config(kernel: str, shape: Tuple[int, ...], tuning: Any = None,
                   device: Optional[str] = None) -> Dict[str, Any]:
    """The config one kernel launch at ``shape`` runs with.

    ``tuning`` selects the source of the config:
      * ``None``  — the checked-in default cache (autotuning ON);
      * ``False`` — autotuning OFF: the roofline-seeded default config;
      * a ``dict`` — an explicit config (tests / the tuner itself);
      * an :class:`AutotuneCache` — that cache (tuner round-trips, CI
        smoke sweeps writing to a scratch path).

    A cached config is fitted to ``shape``
    (:func:`repro.launch.tuning.fit_config`).  Cache misses — including
    cold/corrupt caches and unknown device kinds — and cached configs that
    do not fit ``shape`` fall back to
    :func:`repro.launch.tuning.default_config`.  ``device`` is the device
    kind the cache is keyed by (default: this process's).
    """
    return _resolve(kernel, shape, tuning, device)[0]


# kernel -> what its ops wrapper last launched: the padded shape, the
# config, where the config came from and whether Pallas ran interpreted.
# Read by chip_smoke.py to prove the chip ran compiled kernels.
LAST_DISPATCH: Dict[str, Dict[str, Any]] = {}


def dispatch(kernel: str, shape: Tuple[int, ...], tuning: Any,
             interpret: Optional[bool]) -> Tuple[Dict[str, Any], bool]:
    """The single dispatch point the ops wrappers call per kernel launch:
    the config (see :func:`resolve_config`) and the interpret flag (default:
    interpret everywhere but on a TPU), both recorded in ``LAST_DISPATCH``."""
    import jax
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    cfg, source = _resolve(kernel, shape, tuning)
    LAST_DISPATCH[kernel] = {"shape": tuple(int(d) for d in shape),
                             "config": dict(cfg), "source": source,
                             "interpret": bool(interpret)}
    return cfg, bool(interpret)
