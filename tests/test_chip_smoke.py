"""chip_smoke.py's control flow, guarded on CPU at a tiny size.

The phases run here with the Pallas kernels in interpret mode; on the chip
the same functions run compiled at 100,000 x 1,000.  Also pins the guards
that keep a fallback from passing as a chip run, and where the persistent
compilation cache goes.
"""
import importlib.util
import os
import pathlib

import pytest

import jax

from repro.launch.common import CHECKOUT_CACHE_DIR, enable_compile_cache

ROOT = pathlib.Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny(smoke):
    """1,024 transactions over 64 items: five Apriori levels at 2%."""
    T = smoke.corpus(1024, 64, seed=0)
    apriori, rec = smoke.phase_apriori(T, 0.02, data_plane="pallas",
                                       interpret=True)
    return T, apriori, rec


def _assert_interpreted(smoke, rec, kernels):
    assert rec["backend"] == "pallas"
    assert set(rec["dispatch"]) == set(kernels)
    assert all(d["interpret"] for d in rec["dispatch"].values())
    # the guard main() applies: an interpreted phase is not a chip run
    with pytest.raises(smoke.SmokeFailure, match="interpret mode"):
        smoke.check_compiled(rec)
    assert "smoke timing" in smoke.describe(rec)


def test_apriori_phase_matches_ref(smoke, tiny):
    _, apriori, rec = tiny
    _assert_interpreted(smoke, rec, ["support_count"])
    assert rec["levels"] >= 3
    assert rec["itemsets"] == len(apriori.supports)
    assert rec["rules"] == len(apriori.rules) > 0


def test_eclat_phase_matches_ref_and_apriori(smoke, tiny):
    T, apriori, _ = tiny
    rec = smoke.phase_eclat(T, 0.02, apriori, data_plane="pallas",
                            interpret=True)
    _assert_interpreted(smoke, rec, ["intersect_count"])
    assert rec["itemsets"] == len(apriori.supports)


def test_kernels_phase_runs_several_row_blocks(smoke, tiny):
    T, apriori, _ = tiny
    rec = smoke.phase_kernels(T, apriori.supports, apriori.rules, 128,
                              interpret=True)
    _assert_interpreted(smoke, rec,
                        ["support_count", "intersect_count", "rule_match"])
    for name in ("support_count/mxu", "support_count/packed",
                 "rule_match/mxu", "rule_match/packed"):
        assert rec["row_blocks"][name] >= 2, name


def test_serve_phase_matches_oracle(smoke, tiny):
    _, apriori, _ = tiny
    rec = smoke.phase_serve(apriori.rules, 64, 96, data_plane="pallas",
                            interpret=True)
    _assert_interpreted(smoke, rec, ["rule_match"])
    assert rec["queries"] == 96 and rec["answered"] > 0


def test_sharded_phase_on_one_device(smoke, tiny):
    T, apriori, _ = tiny
    rec = smoke.phase_sharded(T, 0.02, 1, data_plane="pallas",
                              interpret=True)
    _assert_interpreted(smoke, rec, ["support_count"])
    assert rec["shard_devices"] == [jax.devices()[0].id]
    assert rec["itemsets"] == len(apriori.supports)


def test_parity_failure_is_refused(smoke, tiny):
    T, apriori, _ = tiny
    wrong = type(apriori)(supports={}, rules=apriori.rules,
                          report=apriori.report, n_tx=apriori.n_tx)
    with pytest.raises(smoke.SmokeFailure, match="differs from Apriori"):
        smoke.phase_eclat(T, 0.02, wrong, data_plane="ref")


def test_ref_backend_is_refused(smoke):
    rec = {"phase": "apriori", "backend": "ref", "dispatch": {}}
    with pytest.raises(smoke.SmokeFailure, match="resolved to 'ref'"):
        smoke.check_compiled(rec)


def test_platform_guard_refuses_cpu(smoke):
    with pytest.raises(smoke.SmokeFailure, match="not 'tpu'"):
        smoke.require_tpu(jax.devices())


def test_main_on_cpu_exits_nonzero_without_ok_line(smoke, capsys):
    assert smoke.main([]) != 0
    assert '"ok"' not in capsys.readouterr().out


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_compile_cache_defaults_to_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == CHECKOUT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == CHECKOUT_CACHE_DIR
    assert CHECKOUT_CACHE_DIR == str(ROOT / ".jax_cache")


def test_compile_cache_env_wins(monkeypatch, tmp_path, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before   # nothing set
    assert os.listdir(tmp_path) == []
