"""Differential fuzz: every Apriori kernel variant vs the jitted ref vs a
pure-Python (numpy) oracle, under EXACT equality.

The counts are int32 and the rule scores are f32 ``match * conf`` with an
exact 0/1 match factor, so all backends must agree bit-for-bit — any
tolerance would let a subtly-wrong tile config ship as "close enough".
The same bar the autotuner applies per swept config
(:mod:`repro.kernels.autotune.tuner`) is applied here across
hypothesis-generated shapes, densities and tile configs, plus the edge
cases the planes rely on: ``sizes = -1`` padding rows that must never
match, empty candidate/rule sets, and single-word item universes
(``I <= 32``, one packed uint32 lane).
"""
import numpy as np
import pytest
pytest.importorskip("hypothesis")  # optional dev dep; module skips cleanly without it
from hypothesis import given, settings, strategies as st

import jax.numpy as jnp

from repro.kernels.rule_match.ops import rule_topk
from repro.kernels.rule_match.ref import rule_scores_ref
from repro.kernels.support_count.ops import intersect_count, support_count
from repro.kernels.support_count.ref import (intersect_count_ref,
                                             support_count_ref)

# sampled (not arbitrary) dims: every distinct padded shape is a fresh XLA
# compile, so the strategy draws from a small lattice that still crosses
# the interesting boundaries (sub-lane, exact-lane, lane+1, multi-word)
_N_TX = (1, 7, 8, 64, 130)
_N_ITEMS = (1, 20, 32, 33, 128, 200)
_N_CAND = (0, 1, 5, 128, 200)
_TILES = (8, 64, 128, 256, 512)


def np_support_count(T, C):
    """The Python oracle: row t supports candidate c iff c ⊆ t."""
    T = np.asarray(T, np.int64)
    C = np.asarray(C, np.int64)
    dots = T @ C.T                                  # [N, M]
    sizes = C.sum(axis=1)
    return (dots == sizes[None, :]).sum(axis=0).astype(np.int32)


def np_rule_scores(Q, A, sizes, conf):
    """Python oracle for the serving scores: conf where A_r ⊆ q, else 0.
    Padding rows carry sizes = -1; dots are >= 0 so they can never match."""
    dots = np.asarray(Q, np.int64) @ np.asarray(A, np.int64).T
    match = dots == np.asarray(sizes, np.int64)[None, :]
    return (match * np.asarray(conf, np.float32)[None, :]).astype(np.float32)


@st.composite
def support_problems(draw):
    n = draw(st.sampled_from(_N_TX))
    i = draw(st.sampled_from(_N_ITEMS))
    m = draw(st.sampled_from(_N_CAND))
    seed = draw(st.integers(0, 2**31 - 1))
    density = draw(st.sampled_from([0.05, 0.3, 0.9]))
    rng = np.random.default_rng(seed)
    T = (rng.random((n, i)) < density).astype(np.uint8)
    C = np.zeros((m, i), np.uint8)
    for r in range(m):
        C[r, rng.choice(i, size=min(1 + r % 4, i), replace=False)] = 1
    tiles = {"bn": draw(st.sampled_from(_TILES)),
             "bm": draw(st.sampled_from(_TILES)),
             "bi": draw(st.sampled_from(_TILES))}
    return T, C, tiles


@settings(max_examples=25, deadline=None)
@given(support_problems())
def test_support_count_differential(problem):
    T, C, tiles = problem
    want = np_support_count(T, C)
    ref = np.asarray(support_count_ref(jnp.asarray(T), jnp.asarray(C)))
    np.testing.assert_array_equal(ref, want)        # jitted ref vs oracle
    for variant in ("packed", "mxu"):
        got = np.asarray(support_count(
            jnp.asarray(T), jnp.asarray(C),
            tuning={"variant": variant, **tiles}))
        np.testing.assert_array_equal(
            got, want, err_msg=f"variant={variant} tiles={tiles}")


def np_intersect_count(A, B):
    """Python oracle for the Eclat round kernel: popcount(A & B) per row,
    via unpackbits on the raw little-endian bytes (no popcount intrinsic)."""
    bits = np.unpackbits((np.asarray(A) & np.asarray(B)).view(np.uint8),
                         axis=1, bitorder="little")
    return bits.sum(axis=1).astype(np.int32)


@st.composite
def intersect_problems(draw):
    m = draw(st.sampled_from((0, 1, 5, 128, 200)))
    w = draw(st.sampled_from((1, 4, 128, 130)))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    A, B = rng.integers(0, 2**32, size=(2, m, w), dtype=np.uint32)
    tiles = {"bm": draw(st.sampled_from(_TILES)),
             "bw": draw(st.sampled_from(_TILES))}
    return A, B, tiles


@settings(max_examples=25, deadline=None)
@given(intersect_problems())
def test_intersect_count_differential(problem):
    A, B, tiles = problem
    want = np_intersect_count(A, B)
    ref = np.asarray(intersect_count_ref(jnp.asarray(A), jnp.asarray(B)))
    np.testing.assert_array_equal(ref, want)        # jitted ref vs oracle
    got = np.asarray(intersect_count(jnp.asarray(A), jnp.asarray(B),
                                     tuning={"variant": "packed", **tiles}))
    np.testing.assert_array_equal(got, want, err_msg=f"tiles={tiles}")


@st.composite
def rule_problems(draw):
    b = draw(st.sampled_from((1, 3, 8, 16)))
    i = draw(st.sampled_from(_N_ITEMS))
    r = draw(st.sampled_from((0, 1, 5, 128, 200)))
    seed = draw(st.integers(0, 2**31 - 1))
    rng = np.random.default_rng(seed)
    Q = (rng.random((b, i)) < 0.3).astype(np.uint8)
    A = np.zeros((r, i), np.uint8)
    for row in range(r):
        A[row, rng.choice(i, size=min(1 + row % 3, i), replace=False)] = 1
    sizes = A.sum(axis=1).astype(np.float32)
    conf = (rng.random(r) * 0.9 + 0.1).astype(np.float32)
    cons = rng.integers(0, i, size=r).astype(np.int32)
    k = draw(st.sampled_from((1, 3, 5)))
    tiles = {"bb": draw(st.sampled_from(_TILES)),
             "br": draw(st.sampled_from(_TILES)),
             "bi": draw(st.sampled_from(_TILES))}
    return Q, A, sizes, conf, cons, min(k, i), tiles


@settings(max_examples=25, deadline=None)
@given(rule_problems())
def test_rule_topk_differential(problem):
    Q, A, sizes, conf, cons, k, tiles = problem
    n_items = Q.shape[1]
    args = (jnp.asarray(Q), jnp.asarray(A), jnp.asarray(sizes),
            jnp.asarray(conf), jnp.asarray(cons))
    ri, rs = rule_topk(*args, k=k, n_items=n_items, backend="ref")
    outs = {"ref": (np.asarray(ri), np.asarray(rs))}
    for variant in ("packed", "mxu"):
        gi, gs = rule_topk(*args, k=k, n_items=n_items, backend="pallas",
                           tuning={"variant": variant, **tiles})
        outs[variant] = (np.asarray(gi), np.asarray(gs))
    for variant, (gi, gs) in outs.items():
        np.testing.assert_array_equal(
            gi, outs["ref"][0], err_msg=f"items {variant} tiles={tiles}")
        np.testing.assert_array_equal(
            gs, outs["ref"][1], err_msg=f"scores {variant} tiles={tiles}")
    # and the jnp score oracle the ref backend folds through must itself
    # agree with the pure-Python one (closing the differential chain:
    # numpy == jnp ref scores; ref-backend top-k == both Pallas variants)
    np.testing.assert_array_equal(
        np.asarray(rule_scores_ref(jnp.asarray(Q), jnp.asarray(A),
                                   jnp.asarray(sizes), jnp.asarray(conf))),
        np_rule_scores(Q, A, sizes, conf))


# ---------------------------------------------------------------------------
# the planes' contract edges, pinned explicitly (fuzz can miss exact cases)
# ---------------------------------------------------------------------------

def test_support_count_empty_candidates():
    T = (np.random.default_rng(0).random((16, 64)) < 0.4).astype(np.uint8)
    out = np.asarray(support_count(jnp.asarray(T),
                                   jnp.asarray(np.zeros((0, 64), np.uint8))))
    assert out.shape == (0,) and out.dtype == np.int32


@pytest.mark.parametrize("n", [40, 1064])
def test_resident_mxu_tile_exact(n):
    """bn == N and bi == I: the MXU kernel's one-step item grid with the
    whole transaction tile resident for the launch, at N a multiple of 8
    but not of 32 (the int8 sublane tile)."""
    rng = np.random.default_rng(n)
    T = (rng.random((n, 256)) < 0.3).astype(np.uint8)
    C = np.zeros((200, 256), np.uint8)
    for r in range(200):
        C[r, rng.choice(256, size=1 + r % 4, replace=False)] = 1
    got = np.asarray(support_count(
        jnp.asarray(T), jnp.asarray(C),
        tuning={"variant": "mxu", "bn": n, "bm": 128, "bi": 256}))
    np.testing.assert_array_equal(
        got, np.asarray(support_count_ref(jnp.asarray(T), jnp.asarray(C))))
    np.testing.assert_array_equal(got, np_support_count(T, C))


def test_rule_topk_empty_rules():
    Q = (np.random.default_rng(1).random((4, 32)) < 0.4).astype(np.uint8)
    empty = np.zeros((0, 32), np.uint8)
    for variant in ("packed", "mxu"):
        items, scores = rule_topk(
            jnp.asarray(Q), jnp.asarray(empty),
            jnp.asarray(np.zeros(0, np.float32)),
            jnp.asarray(np.zeros(0, np.float32)),
            jnp.asarray(np.zeros(0, np.int32)), k=3, n_items=32,
            backend="pallas",
            tuning={"variant": variant, "bb": 8, "br": 128, "bi": 128})
        assert (np.asarray(scores) <= 0.0).all()    # nothing can match


def test_rule_scores_padding_rows_never_match():
    """sizes = -1 rows (index padding) must score 0 even for an all-zero
    antecedent row against an empty query — the all-zero-matches-everything
    trap the -1 contract exists to close."""
    Q = np.zeros((2, 32), np.uint8)                 # empty baskets
    Q[1, :3] = 1
    A = np.zeros((128, 32), np.uint8)               # all rows all-zero
    sizes = np.full(128, -1.0, np.float32)
    conf = np.ones(128, np.float32)
    for variant in ("packed", "mxu"):
        got = rule_topk(
            jnp.asarray(Q), jnp.asarray(A), jnp.asarray(sizes),
            jnp.asarray(conf), jnp.asarray(np.zeros(128, np.int32)),
            k=3, n_items=32, backend="pallas",
            tuning={"variant": variant, "bb": 8, "br": 128, "bi": 128})[1]
        assert (np.asarray(got) <= 0.0).all(), variant


def test_single_word_universe_exact():
    """I <= 32: the packed layout is one uint32 word — the word-boundary
    edge where a shift/mask bug would first show."""
    rng = np.random.default_rng(7)
    for i in (1, 31, 32):
        T = (rng.random((24, i)) < 0.5).astype(np.uint8)
        C = np.zeros((8, i), np.uint8)
        for r in range(8):
            C[r, rng.choice(i, size=min(1 + r % 3, i), replace=False)] = 1
        want = np_support_count(T, C)
        for variant in ("packed", "mxu"):
            got = np.asarray(support_count(
                jnp.asarray(T), jnp.asarray(C),
                tuning={"variant": variant, "bn": 8, "bm": 128, "bi": 128}))
            np.testing.assert_array_equal(got, want,
                                          err_msg=f"I={i} {variant}")
