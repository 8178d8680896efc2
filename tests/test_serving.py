"""Serving plane: batched top-k vs brute-force oracle, Pallas/ref agreement,
cache + refresh accounting, index determinism and persistence, report
invariants."""
import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.itemsets import apriori
from repro.core.rules import Rule, generate_rules
from repro.data.baskets import BasketConfig, generate_baskets
from repro.kernels.autotune.cache import (LAST_DISPATCH, AutotuneCache,
                                          device_kind)
from repro.kernels.rule_match.ops import rule_topk
from repro.kernels.rule_match.ref import recommend_ref
from repro.pipeline import MarketBasketPipeline, PipelineConfig
from repro.serving import (Query, RecommendationEngine, RuleIndex,
                           ServingConfig, recommend_bruteforce)


@pytest.fixture(scope="module")
def mined():
    """One small mined corpus shared by the engine tests."""
    T = generate_baskets(BasketConfig(n_tx=500, n_items=32, n_patterns=5,
                                      pattern_len=3, pattern_prob=0.5,
                                      seed=3))
    res = MarketBasketPipeline(
        config=PipelineConfig(min_support=0.05, min_confidence=0.5,
                              n_tiles=4)).run(T)
    assert res.rules, "fixture corpus must mine a non-trivial rule set"
    return T, res


def queries_of(T, n):
    return [Query.of(list(np.nonzero(row)[0])) for row in T[:n]]


# ---------------------------------------------------------------------------
# kernel family: ops wrapper (Pallas interpret) vs pure-jnp oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("B,I,R,k", [(5, 40, 17, 3), (8, 128, 128, 5),
                                     (1, 33, 7, 1), (12, 64, 150, 4)])
def test_rule_topk_pallas_matches_ref_oracle(B, I, R, k):
    rng = np.random.default_rng(B * I + R)
    Q = (rng.random((B, I)) < 0.3).astype(np.uint8)
    A = np.zeros((R, I), np.uint8)
    for m in range(R):
        A[m, rng.choice(I, size=rng.integers(1, 4), replace=False)] = 1
    sizes = A.sum(1).astype(np.float32)
    conf = rng.random(R).astype(np.float32)
    cons = rng.integers(0, I, R).astype(np.int32)

    got_i, got_s = rule_topk(Q, A, sizes, conf, cons, k=k, n_items=I,
                             backend="pallas", interpret=True)
    # hand-pad for the pure ref oracle (the same contract ops applies)
    Ip = I + (-I) % 128
    Rp = R + (-R) % 128
    Qp = np.pad(Q, ((0, (-B) % 8), (0, Ip - I)))
    Ap = np.pad(A, ((0, Rp - R), (0, Ip - I)))
    want_i, want_s = recommend_ref(
        jnp.asarray(Qp, jnp.int8), jnp.asarray(Ap, jnp.int8),
        jnp.asarray(np.pad(sizes, (0, Rp - R), constant_values=-1)),
        jnp.asarray(np.pad(conf, (0, Rp - R))),
        jnp.asarray(np.pad(cons, (0, Rp - R), constant_values=Ip)), I, k)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i)[:B])
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s)[:B])


@pytest.fixture(scope="module")
def rich_index():
    """A seeded rule-rich index: 1,100 distinct (antecedent, item) rows
    over 256 items, padded to 1,152 rows (9 x 128, not a power of two),
    and 64 baskets dense enough in the antecedents' items that most of
    them match a rule."""
    rng = np.random.default_rng(16)
    n_items, rules, seen = 256, [], set()
    while len(rules) < 1100:
        ante = tuple(sorted(int(i) for i in rng.choice(
            48, size=int(rng.integers(1, 4)), replace=False)))
        item = int(rng.integers(0, n_items))
        if item in ante or (ante, item) in seen:
            continue
        seen.add((ante, item))
        rules.append(Rule(antecedent=ante, consequent=(item,),
                          support=float(rng.random() * 0.05),
                          confidence=float(0.6 + 0.4 * rng.random()),
                          lift=float(1.0 + rng.random())))
    index = RuleIndex.build(rules, n_items)
    assert (index.n_rows, index.n_rows_padded) == (1100, 1152)
    baskets = np.zeros((64, n_items), np.uint8)
    baskets[:, :48] = rng.random((64, 48)) < 0.15
    baskets[:, 48:] = rng.random((64, n_items - 48)) < 0.03
    return rules, index, baskets


@pytest.mark.parametrize("cached_br", [384, 640, 6272])
@pytest.mark.parametrize("variant", ["mxu", "packed"])
def test_rule_topk_fitted_dispatch_matches_oracles(rich_index, variant,
                                                   cached_br):
    """rule_topk through the fitted dispatch (a cached config whose row
    tile is not a power of two, fitted to the 1,152-row index: 384 stays,
    640 -> 384, 6,272 -> the whole 1,152) equals the brute-force oracle
    and the jnp reference exactly."""
    rules, index, baskets = rich_index
    cache = AutotuneCache()
    cfg = {"variant": variant, "bb": 64, "br": cached_br}
    if variant == "mxu":
        cfg["bi"] = 256
    cache.put("rule_match", (64, 1152, 256), cfg, 1.0, device=device_kind())
    args = [jnp.asarray(x) for x in (baskets, index.ante, index.sizes,
                                     index.conf, index.cons)]
    got_i, got_s = rule_topk(*args, k=5, n_items=256, backend="pallas",
                             interpret=True, tuning=cache)
    rec = LAST_DISPATCH["rule_match"]
    assert rec["source"] == "cache" and rec["shape"] == (64, 1152, 256)
    assert rec["config"]["br"] == (1152 if cached_br > 1152 else 384)
    want_i, want_s = recommend_ref(*args, 256, 5)
    np.testing.assert_array_equal(np.asarray(got_i), np.asarray(want_i))
    np.testing.assert_array_equal(np.asarray(got_s), np.asarray(want_s))
    answered = 0
    for row, items, scores in zip(baskets, np.asarray(got_i),
                                  np.asarray(got_s)):
        got = [(int(i), float(sc)) for i, sc in zip(items, scores)
               if sc > 0.0]
        assert got == recommend_bruteforce(rules, np.nonzero(row)[0], 5)
        answered += bool(got)
    assert answered > len(baskets) // 2       # mostly non-empty answers


def test_index_build_runs_in_its_span(monkeypatch, mined):
    import repro.serving.index as index_mod
    names = []

    class Span:
        def __init__(self, name):
            names.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(index_mod, "TraceAnnotation", Span)
    T, res = mined
    index = RuleIndex.build(res.rules, T.shape[1])
    assert names == ["serve-index-build"]
    assert index.n_rules == len(res.rules) and index.n_rows > 0


def test_rule_topk_padded_rows_never_match():
    # an all-zero antecedent row would subset-match everything if the
    # padding contract (sizes = -1) were broken
    Q = np.ones((2, 16), np.uint8)
    A = np.zeros((1, 16), np.uint8)
    A[0, 3] = 1
    items, scores = rule_topk(Q, A, np.array([1.0], np.float32),
                              np.array([0.9], np.float32),
                              np.array([5], np.int32), k=2, n_items=16,
                              backend="ref")
    # item 5 is already in every basket -> excluded; nothing else scores
    assert (np.asarray(scores) <= 0).all()


# ---------------------------------------------------------------------------
# engine: batched top-k == brute-force oracle, plane agreement
# ---------------------------------------------------------------------------

def test_engine_matches_bruteforce_oracle(mined):
    T, res = mined
    index = RuleIndex.build(res.rules, T.shape[1])
    engine = RecommendationEngine(
        index, config=ServingConfig(k=4, batch_buckets=(1, 8),
                                    data_plane="ref"))
    queries = queries_of(T, 60)
    results, report = engine.serve(queries)
    assert report.n_queries == len(queries)
    for q, got in zip(queries, results):
        assert got == recommend_bruteforce(res.rules, q.payload, 4)
        assert len(got) <= 4
        for item, score in got:
            assert item not in q.payload and score > 0


def test_engine_pallas_and_ref_planes_agree(mined):
    T, res = mined
    index = RuleIndex.build(res.rules, T.shape[1])
    queries = queries_of(T, 16)
    base = dict(k=4, batch_buckets=(8,), cache_size=0)
    ref = RecommendationEngine(
        index, config=ServingConfig(data_plane="ref", **base))
    pallas = RecommendationEngine(
        index, config=ServingConfig(data_plane="pallas", interpret=True,
                                    **base))
    r_ref, rep_ref = ref.serve(queries)
    r_pal, rep_pal = pallas.serve(queries)
    assert rep_ref.backend == "ref" and rep_pal.backend == "pallas"
    assert r_ref == r_pal


def test_engine_accepts_bitmap_and_id_list_queries(mined):
    T, res = mined
    engine = RecommendationEngine(RuleIndex.build(res.rules, T.shape[1]),
                                  config=ServingConfig(k=3,
                                                       data_plane="ref"))
    from_rows, _ = engine.serve([Query.of(row) for row in T[:10]])
    from_ids, _ = engine.serve(queries_of(T, 10))
    assert from_rows == from_ids
    with pytest.raises(ValueError):
        engine.recommend(Query.of([T.shape[1] + 5]))    # id out of range
    with pytest.raises(ValueError):
        engine.serve([Query.of(np.full(T.shape[1], 2, np.uint8))])
    padded = np.zeros(engine.index.n_items_padded, np.uint8)
    padded[engine.index.n_items + 1] = 1            # bit in the lane padding
    with pytest.raises(ValueError):
        engine.serve([Query.of(padded)])
    with pytest.raises(TypeError):
        engine.serve([list(np.nonzero(T[0])[0])])   # bare payload: removed
    with pytest.raises(TypeError):
        engine.submit(T[0])                         # bare bitmap row: removed


# ---------------------------------------------------------------------------
# cache: hit/miss accounting, refresh invalidation
# ---------------------------------------------------------------------------

def test_cache_hits_and_refresh_invalidation(mined):
    T, res = mined
    index = RuleIndex.build(res.rules, T.shape[1])
    engine = RecommendationEngine(
        index, config=ServingConfig(k=4, data_plane="ref", cache_size=256))
    queries = queries_of(T, 20)
    first, rep1 = engine.serve(queries)
    assert rep1.cache_misses > 0
    again, rep2 = engine.serve(queries)
    assert again == first
    assert rep2.cache_hits == len(queries) and rep2.cache_misses == 0
    # refresh swaps the index, bumps the version and drops every entry
    v0 = engine.index.version
    engine.refresh(RuleIndex.build(res.rules, T.shape[1]))
    assert engine.index.version > v0
    _, rep3 = engine.serve(queries)
    assert rep3.cache_hits == 0 and rep3.cache_misses == len(queries)


def test_cache_disabled_still_correct(mined):
    T, res = mined
    index = RuleIndex.build(res.rules, T.shape[1])
    engine = RecommendationEngine(
        index, config=ServingConfig(k=4, data_plane="ref", cache_size=0))
    queries = queries_of(T, 10) * 2                 # repeats cannot hit
    results, rep = engine.serve(queries)
    assert rep.cache_hits == 0 and rep.cache_misses == len(queries)
    assert results[:10] == results[10:]


def test_cache_lru_eviction():
    from repro.serving.cache import ResultCache, basket_key
    cache = ResultCache(maxsize=2)
    keys = [basket_key(np.eye(8, dtype=np.uint8)[i]) for i in range(3)]
    for i, key in enumerate(keys):
        cache.put(key, [(i, 1.0)])
    assert cache.get(keys[0]) is None               # evicted, counted as miss
    assert cache.get(keys[2]) == [(2, 1.0)]
    assert cache.hits == 1 and cache.misses == 1


# ---------------------------------------------------------------------------
# index: deterministic build, save -> load -> identical recommendations
# ---------------------------------------------------------------------------

def test_index_build_is_order_invariant(mined):
    T, res = mined
    shuffled = list(res.rules)
    np.random.default_rng(0).shuffle(shuffled)
    a = RuleIndex.build(res.rules, T.shape[1])
    b = RuleIndex.build(shuffled, T.shape[1])
    assert a.same_arrays(b)
    assert a.n_rows == b.n_rows > 0
    assert a.n_rows_padded % 128 == 0 and a.n_items_padded % 128 == 0


def test_index_save_load_identical_recommendations(tmp_path, mined):
    T, res = mined
    index = RuleIndex.build(res.rules, T.shape[1], version=3)
    index.save(str(tmp_path))
    loaded = RuleIndex.load(str(tmp_path))
    assert loaded.same_arrays(index)
    assert (loaded.n_rows, loaded.n_rules, loaded.n_items, loaded.version) \
        == (index.n_rows, index.n_rules, index.n_items, 3)
    queries = queries_of(T, 12)
    cfg = ServingConfig(k=4, data_plane="ref")
    a, _ = RecommendationEngine(index, config=cfg).serve(queries)
    b, _ = RecommendationEngine(loaded, config=cfg).serve(queries)
    assert a == b


def test_index_rejects_bad_inputs(mined):
    _, res = mined
    with pytest.raises(ValueError):
        RuleIndex.build(res.rules, 2)               # rules reference item >= 2
    with pytest.raises(ValueError):
        RuleIndex.build(res.rules, 32, r_bucket=100)  # not a lane multiple
    empty = RuleIndex.build([], 32)                 # legal: all-padding index
    assert empty.n_rows == 0 and empty.n_rows_padded == 128
    engine = RecommendationEngine(empty, config=ServingConfig(
        k=3, data_plane="ref"))
    assert engine.recommend(Query.of([0, 1])) == []


# ---------------------------------------------------------------------------
# report invariants
# ---------------------------------------------------------------------------

def test_serving_report_invariants(mined):
    T, res = mined
    index = RuleIndex.build(res.rules, T.shape[1])
    engine = RecommendationEngine(
        index, config=ServingConfig(k=4, batch_buckets=(1, 8),
                                    data_plane="ref"))
    n = 30
    arrival = np.linspace(0.0, 100.0, n)
    _, rep = engine.serve(queries_of(T, n), arrival_s=arrival)
    assert rep.n_queries == n
    assert 0 < rep.batch_fill <= 1.0
    assert rep.p50_latency_s <= rep.p99_latency_s
    assert rep.sim_time_s > 0 and rep.qps > 0
    assert rep.energy_j > 0 and rep.switches >= 0
    assert sum(rep.bucket_counts.values()) == rep.n_batches
    assert rep.cache_hits + rep.cache_misses == n
    assert "QPS" in rep.summary()
    with pytest.raises(ValueError):
        engine.serve(queries_of(T, 3), arrival_s=[2.0, 1.0, 3.0])


# ---------------------------------------------------------------------------
# satellite: rule ordering is a reproducible total order
# ---------------------------------------------------------------------------

def test_generate_rules_order_independent_of_supports_insertion():
    T = generate_baskets(BasketConfig(n_tx=300, n_items=16, n_patterns=3,
                                      pattern_len=3, pattern_prob=0.6,
                                      seed=2))
    res = apriori(T, min_support=15)
    rules = generate_rules(res, min_confidence=0.3)
    # same supports, reversed dict insertion order -> identical rule list
    import dataclasses
    rev = dataclasses.replace(
        res, supports=dict(reversed(list(res.supports.items()))))
    assert generate_rules(rev, min_confidence=0.3) == rules
    # the sort key is a total order over the rule tuple itself
    keys = [(-r.confidence, -r.support, -r.lift, r.antecedent, r.consequent)
            for r in rules]
    assert keys == sorted(keys)
    assert len(set(keys)) == len(keys)
