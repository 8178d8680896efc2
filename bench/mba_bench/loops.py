"""The two loops that drive the system under test, one per traffic kind.

Each loop has the same four steps, in the order the harness calls them:

``setup()``    builds the system from the configuration and warms every
               shape the window will use (timed as set-up);
``window()``   the measured window, optionally traced at its start;
``free()``     drops every reference to the system's device state;
``check()``    compares what the window produced with the plain reference.

The entries the windows drive are the ones users call:
``make_miner(...).run`` for ``mine_loop`` and ``AsyncServer.submit`` for
``open_loop``.
"""
from __future__ import annotations

import gc
import sys
import time
from dataclasses import dataclass, field
from typing import Dict, List

import numpy as np

from mba_bench import reference, traffic

TRACE_MINES = 1          # mines traced at the start of a traced window
TRACE_SECONDS = 3.0      # seconds of serving traced at the window's start
GRACE_SECONDS = 60.0     # how long an answer may come after the close


@dataclass
class Check:
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value <= self.limit


@dataclass
class Outcome:
    """What a window produced, for the metrics and the result line."""

    e2e: Dict[str, float]
    attempted: int
    failed: int = 0
    checks: List[Check] = field(default_factory=list)


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _pipeline_config(cfg: Dict):
    from repro.pipeline import PipelineConfig
    return PipelineConfig(**cfg["mining"])


class MineLoop:
    kind = "mine_loop"

    def __init__(self, cfg: Dict, spec: Dict, T: np.ndarray, model,
                 seed: int):
        self.cfg, self.T = cfg, T
        self.mines: List = []           # window results, in order
        self.traced = 0                 # mines inside the traced span

    def setup(self, seconds: float) -> None:
        from repro.mining import make_miner
        self.miner, _ = make_miner(self.T, config=_pipeline_config(self.cfg))
        self.miner.run(self.T)          # compiles every shape of the lattice

    def window(self, seconds: float, tracer) -> None:
        tracer.start()
        t0 = time.perf_counter()
        while True:
            self.mines.append(self.miner.run(self.T))
            if tracer.active and len(self.mines) >= TRACE_MINES:
                self.traced = len(self.mines)
                tracer.stop()
            if time.perf_counter() - t0 >= seconds:
                break
        self.elapsed = time.perf_counter() - t0
        if tracer.active:
            self.traced = len(self.mines)
            tracer.stop()

    def free(self) -> None:
        del self.miner
        gc.collect()

    def e2e(self) -> Dict[str, float]:
        return {"mine_s": self.elapsed / len(self.mines)}

    def check(self) -> Outcome:
        mining = self.cfg["mining"]
        t = time.perf_counter()
        self.ref = reference.mine(self.T, mining["min_support"],
                                  mining["min_confidence"])
        _log(f"reference mine {time.perf_counter() - t:.3f} s: "
             f"{len(self.ref.supports)} itemsets, {len(self.ref.rules)} "
             f"rules, levels (candidates, items) {self.ref.levels}")
        sup_bad = rule_bad = failed = 0
        for res in self.mines:
            s = reference.mismatches(res.supports, self.ref.supports)
            r = reference.mismatches(rule_map(res.rules), self.ref.rules)
            sup_bad += s
            rule_bad += r
            failed += bool(s or r)
        return Outcome(self.e2e(), attempted=len(self.mines), failed=failed,
                       checks=[Check("support_mismatch", sup_bad, 0),
                               Check("rule_mismatch", rule_bad, 0)])


def rule_map(rules) -> Dict:
    return {(r.antecedent, r.consequent): (r.support, r.confidence, r.lift)
            for r in rules}


class OpenLoop:
    kind = "open_loop"

    def __init__(self, cfg: Dict, spec: Dict, T: np.ndarray, model,
                 seed: int):
        self.cfg, self.spec, self.T, self.model = cfg, spec, T, model
        self.seed = seed

    def setup(self, seconds: float) -> None:
        from repro.mining import make_miner
        from repro.serving import (AsyncServer, Query, RecommendationEngine,
                                   RuleIndex, ServingConfig)
        miner, _ = make_miner(self.T, config=_pipeline_config(self.cfg))
        self.mined = miner.run(self.T)
        del miner
        n_items = self.cfg["quest"]["N"]
        scfg = dict(self.cfg["serving"])
        scfg["batch_buckets"] = tuple(scfg["batch_buckets"])
        self.index = RuleIndex.build(self.mined.rules, n_items)
        _log(f"rule index: {len(self.mined.rules)} rules, "
             f"{self.index.n_rows} rows ({self.index.n_rows_padded} padded)")
        self.engine = RecommendationEngine(self.index,
                                           config=ServingConfig(**scfg))
        self.server = AsyncServer(self.engine)      # warms the bucket ladder
        rng = np.random.default_rng([int(self.seed), 3])
        n = traffic.n_requests(self.spec, seconds)
        self.offsets = traffic.arrival_offsets(self.spec, n, seconds, rng)
        self.baskets = traffic.baskets(self.spec, self.model, n, rng)
        # one pass of the drain thread over every bucket before the window
        warm = traffic.baskets(self.spec, self.model, 2 * max(
            scfg["batch_buckets"]), np.random.default_rng([int(self.seed),
                                                           4]))
        self.server.start()
        for burst in (1, 8, len(warm) // 2):
            hs = [self.server.submit(Query.of(row)) for row in warm[:burst]]
            for h in hs:
                h.result(timeout=GRACE_SECONDS)
            warm = warm[burst:]
        self.server.stop()
        self.server.take_report()
        self.engine.cache.clear()

    def window(self, seconds: float, tracer) -> None:
        from repro.serving import Query
        server = self.server
        first_step = len(server._steps)
        server.start()
        clock = server.clock
        tracer.start()
        base = clock.now() + 0.01
        due = (base + self.offsets).tolist()
        n = len(due)
        handles = [None] * n
        lag = np.zeros(n)
        i = 0
        trace_end = base + TRACE_SECONDS
        self.trace_span = (base, base)
        while i < n:
            now = clock.now()
            if tracer.active and now >= trace_end:
                tracer.stop()
                self.trace_span = (base, clock.now())
            if due[i] > now:
                time.sleep(min(due[i] - now, 0.002))
                continue
            while i < n and due[i] <= now:
                # each request is built when it is sent, as a shopper's is
                handles[i] = server.submit(Query.of(self.baskets[i]),
                                           arrival_s=due[i])
                lag[i] = clock.now() - due[i]
                i += 1
        if tracer.active:
            tracer.stop()
            self.trace_span = (base, clock.now())
        deadline = time.perf_counter() + GRACE_SECONDS
        for h in handles:
            try:
                h.result(timeout=max(deadline - time.perf_counter(), 0.0))
            except RuntimeError:
                pass                      # shed or never answered: failed
        server.stop()
        self.handles = handles
        self.base, self.seconds = base, seconds
        self.report = server.take_report()
        self.steps = server._steps[first_step:]      # the window's steps
        worst = int(lag.argmax())
        _log(f"generator lag p50 {np.percentile(lag, 50) * 1e3:.3f} ms, "
             f"p99 {np.percentile(lag, 99) * 1e3:.3f} ms, "
             f"max {lag.max() * 1e3:.3f} ms (request due "
             f"{self.offsets[worst]:.3f} s into the window) over {n} "
             f"requests")

    def free(self) -> None:
        del self.server, self.engine
        gc.collect()

    def e2e(self) -> Dict[str, float]:
        done = [h for h in self.handles if h.status == "done"]
        lat = np.array([h.latency_s for h in done]) * 1e3
        close = self.base + self.seconds
        in_window = sum(1 for h in done if h.done_s <= close)
        out = {"serve_qps": in_window / self.seconds}
        if len(lat):
            out["serve_p50_ms"] = float(np.percentile(lat, 50))
            out["serve_p99_ms"] = float(np.percentile(lat, 99))
        return out

    def check(self) -> Outcome:
        mining = self.cfg["mining"]
        self.ref = reference.mine(self.T, mining["min_support"],
                                  mining["min_confidence"])
        sup_bad = reference.mismatches(self.mined.supports, self.ref.supports)
        rule_bad = reference.mismatches(rule_map(self.mined.rules),
                                        self.ref.rules)
        t = time.perf_counter()
        want = reference.topk(self.baskets, self.ref.rules,
                              self.cfg["quest"]["N"], self.cfg["serving"]["k"])
        _log(f"reference top-k of {len(want)} baskets "
             f"{time.perf_counter() - t:.3f} s")
        unanswered = wrong = 0
        for h, w in zip(self.handles, want):
            if h.status != "done":
                unanswered += 1
            elif h.result() != w:
                wrong += 1
        return Outcome(self.e2e(), attempted=len(self.handles),
                       failed=unanswered + wrong,
                       checks=[Check("support_mismatch", sup_bad, 0),
                               Check("rule_mismatch", rule_bad, 0),
                               Check("topk_mismatch", wrong, 0),
                               Check("unanswered", unanswered, 0)])


LOOPS = {"mine_loop": MineLoop, "open_loop": OpenLoop}
