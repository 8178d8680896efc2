"""The comparison catches a broken timed path: each fault is planted in
the system under test, the rest of a run goes as on the chip (without the
look for one), and ``correct`` has to come out false."""
import pytest

import bench_fixtures as fx


def _half_batch_mine(mp):
    from repro.pipeline.pipeline import MarketBasketPipeline, ingest_baskets
    mp.setattr(MarketBasketPipeline, "_ingest",
               lambda self, b: ingest_baskets(b[:len(b) // 2]))


def _state_unchanged_mine(mp):
    import repro.pipeline.pipeline as pipeline
    mp.setattr(pipeline, "donated_add", lambda acc, part: acc)


def _answer_altered_mine(mp):
    from repro.pipeline.devgen import DeviceLattice
    real = DeviceLattice.decode_supports

    def decode_supports(self):
        out = real(self)
        if out:
            key = next(iter(out))
            out[key] += 1
        return out
    mp.setattr(DeviceLattice, "decode_supports", decode_supports)


def _half_batch_serve(mp):
    from repro.serving.engine import RecommendationEngine
    real = RecommendationEngine._score_batch

    def _score_batch(self, rows, bucket):
        half = (len(rows) + 1) // 2
        return real(self, rows[:half], bucket) + [[]] * (len(rows) - half)
    mp.setattr(RecommendationEngine, "_score_batch", _score_batch)


def _answer_altered_serve(mp):
    from repro.serving.engine import RecommendationEngine
    real = RecommendationEngine._score_batch

    def _score_batch(self, rows, bucket):
        return [rec[:-1] for rec in real(self, rows, bucket)]
    mp.setattr(RecommendationEngine, "_score_batch", _score_batch)


@pytest.mark.parametrize("cell,plant,check", [
    (fx.MINE, _half_batch_mine, "support_mismatch"),
    (fx.MINE, _state_unchanged_mine, "support_mismatch"),
    (fx.MINE, _answer_altered_mine, "support_mismatch"),
    (fx.SERVE, _half_batch_serve, "topk_mismatch"),
    (fx.SERVE, _answer_altered_serve, "topk_mismatch"),
], ids=["mine-half-batch", "mine-state-unchanged", "mine-answer-altered",
        "serve-half-batch", "serve-answer-altered"])
def test_planted_fault_reads_wrong(monkeypatch, tmp_path, cell, plant, check):
    fx.use_fixture_files(monkeypatch, tmp_path)
    plant(monkeypatch)
    line = fx.run(fx.fixture_benchmark(), cell)
    assert line["correct"] is False
    assert line["checks"][check]["value"] > 0
    assert line["failed"] > 0
