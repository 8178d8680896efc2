"""Async serving plane: scripted-clock admission/coalescing/SLO unit tests,
exactly-once drain delivery, async == closed-loop == brute-force parity
under both switching policies, PlaneReport protocol conformance, and the
threaded wall-clock mode."""
import numpy as np
import pytest

from repro.data.baskets import BasketConfig, generate_baskets
from repro.pipeline import MarketBasketPipeline, PipelineConfig
from repro.runtime import PlaneReport
from repro.serving import (AsyncServer, BucketLadder, Handle, Query,
                           RecommendationEngine, RequestQueue, RuleIndex,
                           ServingConfig, ShedError, SloGovernor,
                           VirtualClock, WallClock, recommend_bruteforce)
from repro.serving.cache import basket_key


@pytest.fixture(scope="module")
def mined():
    """One small mined corpus shared by the async serving tests."""
    T = generate_baskets(BasketConfig(n_tx=500, n_items=32, n_patterns=5,
                                      pattern_len=3, pattern_prob=0.5,
                                      seed=3))
    res = MarketBasketPipeline(
        config=PipelineConfig(min_support=0.05, min_confidence=0.5,
                              n_tiles=4)).run(T)
    assert res.rules, "fixture corpus must mine a non-trivial rule set"
    return T, res


def make_engine(res, policy="static", buckets=(1, 8, 64), cache_size=0,
                slo_ms=0.0, n_items=32):
    return RecommendationEngine(
        RuleIndex.build(res.rules, n_items),
        config=ServingConfig(k=5, batch_buckets=buckets, data_plane="ref",
                             cache_size=cache_size, policy=policy,
                             slo_ms=slo_ms))


def queries_of(T, n):
    return [Query.of(list(np.nonzero(row)[0])) for row in T[:n]]


def handle_of(rid, arrival_s, n_items=8):
    bits = np.zeros(n_items, dtype=np.uint8)
    return Handle(rid=rid, query=Query([0]), arrival_s=arrival_s,
                  bits=bits, key=basket_key(bits))


# ---------------------------------------------------------------------------
# admission pieces under a scripted clock (no engine, no jax)
# ---------------------------------------------------------------------------

def test_request_queue_fifo_and_arrival_gating():
    q = RequestQueue()
    for rid, t in enumerate([0.0, 1.0, 2.0]):
        q.append(handle_of(rid, t))
    assert q.next_arrival() == 0.0
    # only the contiguous head that has arrived by now is taken
    got = q.take_ready(now=1.5, limit=10)
    assert [h.rid for h in got] == [0, 1]
    assert len(q) == 1 and q.next_arrival() == 2.0
    # the limit is the slot count: a full queue yields at most `limit`
    for rid in range(3, 9):
        q.append(handle_of(rid, 2.0))
    got = q.take_ready(now=5.0, limit=4)
    assert [h.rid for h in got] == [2, 3, 4, 5]


def test_bucket_ladder_pick_coalesces_to_smallest_cover():
    ladder = BucketLadder([64, 1, 8, 8])      # deduped + sorted
    assert ladder.buckets == (1, 8, 64)
    assert [ladder.pick(n) for n in (1, 2, 8, 9, 64)] == [1, 8, 8, 64, 64]
    with pytest.raises(ValueError):
        ladder.pick(65)
    with pytest.raises(ValueError):
        ladder.pick(0)


def test_bucket_ladder_warm_and_ewma_projection():
    ladder = BucketLadder([1, 4])
    clock = iter(np.arange(0.0, 10.0, 0.5))   # scripted timer: 0.5s/rung
    warmed = []
    total = ladder.warm(warmed.append, lambda: float(next(clock)))
    assert warmed == [1, 4] and total == pytest.approx(1.0)
    assert ladder.warmed and ladder.state[1].warm_wall_s == 0.5
    # nothing measured yet -> projections come from warm-free fallback (0)
    # until observe() feeds real steps
    ladder.observe(1, 2.0)
    assert ladder.projected_step_s(1) == pytest.approx(2.0)
    # unmeasured rung projects from the nearest measured one, ratio-scaled
    assert ladder.projected_step_s(4) == pytest.approx(8.0)
    ladder.observe(1, 1.0)                    # EWMA alpha=0.3
    assert ladder.projected_step_s(1) == pytest.approx(0.3 * 1.0 + 0.7 * 2.0)


def test_slo_governor_sheds_at_scripted_threshold():
    ladder = BucketLadder([1, 8])
    gov = SloGovernor(slo_s=1.0, ladder=ladder)
    late, fresh = handle_of(0, 0.0), handle_of(1, 0.7)
    # no measurements yet -> the governor only acts on evidence: admit all
    admit, shed = gov.split(now=0.8, ready=[late, fresh])
    assert [h.rid for h in admit] == [0, 1] and not shed
    # scripted step walls: one step on the covering bucket takes 0.5s
    ladder.observe(8, 0.5)
    admit, shed = gov.split(now=0.8, ready=[late, fresh])
    # late: 0.8 queue delay + 0.5 step = 1.3 > 1.0 -> shed;
    # fresh: 0.1 + 0.5 = 0.6 <= 1.0 -> admitted
    assert [h.rid for h in shed] == [0]
    assert [h.rid for h in admit] == [1]
    assert gov.n_shed == 1
    # slo_s <= 0 disables shedding entirely
    assert SloGovernor(0.0, ladder).split(5.0, [late])[1] == []


def test_handle_finishes_exactly_once():
    h = handle_of(0, 0.0)
    with pytest.raises(RuntimeError, match="pending"):
        h.result()
    h._finish("done", [(1, 0.5)], t_done=2.0)
    assert h.done() and h.latency_s == pytest.approx(2.0)
    assert h.result() == [(1, 0.5)]
    with pytest.raises(AssertionError):      # terminal transition is single
        h._finish("done", [], 3.0)
    s = handle_of(1, 0.0)
    s._finish("shed", None, 1.0)
    with pytest.raises(ShedError):
        s.result()


def test_query_coercion_forms():
    q = Query.of([3, 7])
    assert q.payload == [3, 7] and q.rid is None
    q = Query.of({"items": [3, 7], "id": 42, "arrival_s": 1.5})
    assert (q.payload, q.rid, q.arrival_s) == ([3, 7], 42, 1.5)
    assert Query.of(q) is q                   # idempotent
    with pytest.raises(ValueError, match="items"):
        Query.of({"basket": [1]})
    with pytest.raises(ValueError, match="allow only"):
        Query.of({"items": [1], "priority": 9})


def test_clock_domains():
    v = VirtualClock()
    assert v.domain == "sim" and v.now() == 0.0
    assert v.advance(2.0) == 2.0
    assert v.advance(1.0) == 2.0              # never backwards
    w = WallClock()
    assert w.domain == "wall" and w.advance(1e9) < 1.0   # advance is a no-op


# ---------------------------------------------------------------------------
# the drain loop on a real engine (virtual clock: fully deterministic)
# ---------------------------------------------------------------------------

def test_admission_fills_slots_then_runs(mined):
    T, res = mined
    engine = make_engine(res, buckets=(1, 2, 4))
    server = AsyncServer(engine, slots=2)
    for q in queries_of(T, 5):                # all arrive at t=0
        server.submit(q)
    assert len(server.drain()) == 5
    rep = server.take_report()
    # 5 ready requests through 2 slots = steps of 2, 2, 1
    assert rep.n_steps == 3
    assert rep.bucket_counts == {2: 2, 1: 1}
    assert rep.slot_occupancy == pytest.approx(np.mean([1.0, 1.0, 0.5]))
    assert rep.batch_fill == pytest.approx(1.0)   # every bucket exactly full


def test_coalescing_never_strands_a_request(mined):
    T, res = mined
    engine = make_engine(res, buckets=(1, 8, 64))
    server = AsyncServer(engine)
    # a lone request, then long-gapped stragglers: each must be scored on
    # the smallest covering bucket as soon as it arrives, never held for
    # a full batch
    arrivals = [0.0, 100.0, 200.0, 300.0]
    handles = [server.submit(q, arrival_s=t)
               for q, t in zip(queries_of(T, 4), arrivals)]
    assert len(server.drain()) == 4
    rep = server.take_report()
    assert all(h.status == "done" for h in handles)
    assert rep.bucket_counts == {1: 4}        # coalesced, not padded to 64
    for h in handles:                         # nobody waited on a neighbor
        assert h.latency_s < 100.0


def test_drain_delivers_every_request_exactly_once(mined):
    T, res = mined
    engine = make_engine(res)
    server = AsyncServer(engine)
    qs = queries_of(T, 6)
    first = [server.submit(q) for q in qs[:4]]
    got1 = server.drain()
    assert got1 == first                      # submission order
    second = [server.submit(q) for q in qs[4:]]
    got2 = server.drain()
    assert got2 == second                     # no re-delivery of the first 4
    assert server.drain() == []               # idle drain yields nothing
    rids = [h.rid for h in got1 + got2]
    assert len(rids) == len(set(rids)) == 6


def test_slo_shedding_on_the_server(mined):
    T, res = mined
    engine = make_engine(res, slo_ms=1000.0)
    server = AsyncServer(engine)
    qs = queries_of(T, 3)
    # script the projection: a step on any rung takes 0.5s
    for b in server.ladder.buckets:
        server.ladder.observe(b, 0.5)
    # one request already 0.8s old when the loop first runs, one fresh
    late = server.submit(qs[0], arrival_s=0.0)
    fresh = server.submit(qs[1], arrival_s=0.8)
    server.clock.advance(0.8)
    server.drain()
    assert late.status == "shed" and fresh.status == "done"
    with pytest.raises(ShedError, match="shed"):
        late.result()
    rep = server.take_report()
    assert rep.n_shed == 1 and rep.n_completed == 1
    # the shed is a first-class priced phase in the ledger, kind="shed"
    sheds = rep.ledger.by_kind("shed")
    assert len(sheds) == 1 and sheds[0].energy_j > 0
    assert rep.shed_rate == pytest.approx(0.5)
    # a request submitted after load subsides is served normally
    ok = server.submit(qs[2])
    assert server.poll(ok) is not None


def test_async_matches_closed_loop_and_oracle_under_both_policies(mined):
    T, res = mined
    qs = queries_of(T, 48)
    rng = np.random.default_rng(11)
    arrivals = np.cumsum(rng.exponential(0.05, size=48))
    oracle = [recommend_bruteforce(res.rules, q.payload, 5) for q in qs]
    for policy in ("static", "dynamic"):
        closed, crep = make_engine(res, policy=policy).serve(qs, arrivals)
        engine = make_engine(res, policy=policy)
        server = AsyncServer(engine)
        handles = [server.submit(q, arrival_s=float(t))
                   for q, t in zip(qs, arrivals)]
        server.drain()
        rep = server.take_report()
        got = [h.result() for h in handles]
        assert got == closed == oracle, f"policy={policy}"
        # same trace, same loop: identical accounting, not just results
        assert rep.total_energy_j == pytest.approx(crep.energy_j)
        assert rep.total_switches == crep.switches
        assert rep.p99_latency_s == pytest.approx(crep.p99_latency_s)
        assert rep.ledger.n_phases == crep.ledger.n_phases
        assert set(p.kind for p in rep.ledger.phases) <= {"serial", "map"}
        assert engine.runtime.ledger.n_phases == 0   # slices fully taken


def test_ladder_rewarms_after_index_refresh(mined):
    T, res = mined
    engine = make_engine(res)
    server = AsyncServer(engine)
    v0 = server._warm_version
    assert server.ladder.warmed and v0 == engine.index.version
    h1 = server.submit(queries_of(T, 1)[0])
    assert server.poll(h1) is not None
    engine.refresh(RuleIndex.build(res.rules[: len(res.rules) // 2], 32))
    h2 = server.submit(queries_of(T, 1)[0])
    assert server.poll(h2) is not None
    assert server._warm_version == engine.index.version > v0
    rep = server.take_report()
    assert rep.index_version == engine.index.version


def test_engine_submit_poll_drain_surface(mined):
    T, res = mined
    engine = make_engine(res, cache_size=64)
    q = queries_of(T, 1)[0]
    h = engine.submit({"items": q.payload, "id": 99})
    assert h.rid == 99
    want = recommend_bruteforce(res.rules, q.payload, 5)
    assert engine.poll(h) == want
    h2 = engine.submit(q)                     # server-assigned rid moves on
    assert h2.rid > 99
    done = engine.drain()
    assert [x.rid for x in done] == [99, h2.rid]
    assert h2.result() == want


def test_plane_report_protocol_conformance(mined):
    T, res = mined
    engine = make_engine(res)
    _, srep = engine.serve(queries_of(T, 4))
    server = AsyncServer(engine)
    server.submit(queries_of(T, 1)[0])
    server.drain()
    arep = server.take_report()
    for report in (res.report, srep, arep):   # pipeline, serving, async
        assert isinstance(report, PlaneReport), type(report)
        assert report.total_time_s >= 0 and report.total_energy_j >= 0
        assert isinstance(report.summary(), str)
    from repro.streaming.miner import StreamingReport
    stream_rep = StreamingReport(backend="ref", policy="static", split="lpt",
                                 window=8, batch_size=4)
    assert isinstance(stream_rep, PlaneReport)


def test_threaded_wall_clock_mode(mined):
    T, res = mined
    qs = queries_of(T, 12)
    inline, _ = make_engine(res).serve(qs)
    engine = make_engine(res)
    with AsyncServer(engine) as server:       # start()s the drain thread
        handles = [server.submit(q) for q in qs]
        results = [h.result(timeout=30.0) for h in handles]
    assert results == inline                  # batching never changes answers
    rep = server.take_report()
    assert rep.clock == "wall"
    assert rep.n_completed == 12 and rep.n_shed == 0
    assert rep.p99_latency_s > 0


# ---------------------------------------------------------------------------
# measurement: queue wait vs service, every phase timed, profiler spans
# ---------------------------------------------------------------------------

def test_queue_wait_and_service_split_on_the_virtual_clock(mined):
    T, res = mined
    server = AsyncServer(make_engine(res, buckets=(1, 8)), warm=False)
    handles = [server.submit(q, arrival_s=0.001 * i)
               for i, q in enumerate(queries_of(T, 12))]
    server.drain()
    assert all(h.arrival_s <= h.taken_s <= h.done_s for h in handles)
    # a request that arrived mid-step waited for the next one
    assert any(h.taken_s > h.arrival_s for h in handles)
    assert all(h.latency_s == pytest.approx(
        (h.taken_s - h.arrival_s) + (h.done_s - h.taken_s))
        for h in handles)


def test_queue_wait_and_service_split_on_the_wall_clock(mined):
    T, res = mined
    with AsyncServer(make_engine(res)) as server:
        handles = [server.submit(q) for q in queries_of(T, 12)]
        for h in handles:
            h.result(timeout=30.0)
    assert server.clock.domain == "wall"
    assert all(h.arrival_s <= h.taken_s <= h.done_s for h in handles)


def test_admit_and_shed_phases_time_their_work(mined):
    T, res = mined
    engine = make_engine(res, slo_ms=1000.0)
    server = AsyncServer(engine)
    for b in server.ladder.buckets:
        server.ladder.observe(b, 0.5)
    qs = queries_of(T, 2)
    late = server.submit(qs[0], arrival_s=0.0)
    fresh = server.submit(qs[1], arrival_s=0.8)
    server.clock.advance(0.8)
    server.drain()
    assert late.status == "shed" and fresh.status == "done"
    phases = server.take_report().ledger.phases
    assert [p.name for p in phases] == ["serve-shed-0", "serve-admit-0",
                                        "serve-score-0"]
    assert all(p.host_time_s > 0 for p in phases)
    # on the modelled axis a reject completes at the end of its triage
    shed = phases[0]
    assert late.taken_s == 0.8
    assert late.done_s == pytest.approx(0.8 + shed.sim_time_s)
    assert fresh.done_s == pytest.approx(
        0.8 + sum(p.sim_time_s for p in phases))


def _bench_trace_module():
    """The benchmark's trace reduction (``bench/mba_bench/trace.py``)."""
    import os
    import sys
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench")
    if bench not in sys.path:
        sys.path.insert(0, bench)
    from mba_bench import trace
    return trace


def test_profiler_trace_holds_the_phase_spans(mined, tmp_path):
    import jax
    trace = _bench_trace_module()
    T, res = mined
    pipe = MarketBasketPipeline(config=PipelineConfig(
        min_support=0.05, min_confidence=0.5, n_tiles=4))
    engine = make_engine(res)
    jax.profiler.start_trace(str(tmp_path))
    try:
        pipe.run(T)
        with AsyncServer(engine) as server:
            for h in [server.submit(q) for q in queries_of(T, 4)]:
                h.result(timeout=30.0)
    finally:
        jax.profiler.stop_trace()
    tr = trace.load(trace.find_xplane(str(tmp_path)))
    names = {e.name for e in tr.host_events}
    assert {"mba-ingest", "mba-upload", "mba-round2-support",
            "mba-candgen-k2", "mba-rules", "serve-wait", "serve-step",
            "serve-admit", "serve-score", "serve-pad", "serve-dispatch",
            "serve-readback", "serve-decode"} <= names, sorted(names)
