"""Analysis-layer tests: config bookkeeping (param counts, block
patterns) and the invariant analyzer."""
import pytest

from repro.configs import ARCH_IDS, get_config


# --------------------------------------------------------------------------- #
# config bookkeeping
# --------------------------------------------------------------------------- #

@pytest.mark.parametrize("arch,expected_params_b", [
    ("starcoder2_3b", (2.5, 3.5)),
    ("mixtral_8x22b", (125, 150)),       # total (all experts)
    ("falcon_mamba_7b", (6.5, 8.0)),
    ("minitron_8b", (7.5, 9.5)),
    ("phi4_mini_3_8b", (3.6, 4.0)),
    ("minitron_4b", (4.0, 4.4)),
    ("jamba_v0_1_52b", (50, 54)),        # total (all experts)
    ("whisper_medium", (0.72, 0.80)),
    # the language model only (1.5 B on the model card); the 675 M vision
    # encoder is not part of this configuration
    ("qwen2_vl_2b", (1.4, 1.7)),
])
def test_param_counts_in_published_range(arch, expected_params_b):
    n = get_config(arch).param_count() / 1e9
    lo, hi = expected_params_b
    assert lo <= n <= hi, f"{arch}: {n:.2f}B outside [{lo},{hi}]"


def test_mixtral_active_params_much_smaller():
    cfg = get_config("mixtral_8x22b")
    assert cfg.param_count(active_only=True) < cfg.param_count() * 0.4


def test_jamba_block_pattern():
    cfg = get_config("jamba_v0_1_52b")
    pat = cfg.block_pattern()
    assert len(pat) == 8
    assert sum(1 for s in pat if s.mixer == "attn") == 1      # 1:7 interleave
    assert sum(1 for s in pat if s.ffn == "moe") == 4         # every other


def test_jamba2_layout_and_params():
    """Jamba2-3B: one period of 14 layers scanned twice, attention at slot
    7 only, a dense MLP on every layer; the parameter count (inner norms
    included) is the published model's and the built tree's."""
    import jax
    from repro.models import transformer
    cfg = get_config("jamba2_3b")
    pat = cfg.block_pattern()
    assert [i for i, s in enumerate(pat) if s.mixer == "attn"] == [7]
    assert {s.ffn for s in pat} == {"mlp"} and cfg.num_periods() == 2
    leaves = jax.tree.leaves(transformer.abstract_params(cfg))
    assert cfg.param_count() == sum(a.size for a in leaves) == 3_029_337_472


def test_layers_divisible_by_period():
    for a in ARCH_IDS:
        cfg = get_config(a)
        assert cfg.num_layers % cfg.period() == 0


# --------------------------------------------------------------------------- #
# invariant analyzer (repro.analysis): each check fires on its fixture
# violation, passes on the corrected twin, the allowlist is honored, and
# the real tree is clean
# --------------------------------------------------------------------------- #
import dataclasses
import os as _os
import subprocess
import sys

from repro.analysis import CHECK_NAMES, module_name, run_checks
from repro.analysis.cachesan import (CacheDivergence, CacheSanitizer,
                                     sanitizer_self_test)

HERE = _os.path.dirname(_os.path.abspath(__file__))
ROOT = _os.path.dirname(HERE)
FIX = _os.path.join(HERE, "fixtures", "analysis")
BAD = _os.path.join(FIX, "bad")
GOOD = _os.path.join(FIX, "good")


def _bad(*rel):
    return _os.path.join(BAD, "src", "repro", *rel)


def _good(*rel):
    return _os.path.join(GOOD, "src", "repro", *rel)


def _checks_of(paths, checks=CHECK_NAMES):
    return [v.check for v in run_checks([paths] if isinstance(paths, str)
                                        else paths, checks).violations]


def test_module_name_derivation():
    assert module_name("src/repro/core/executor.py") == "repro.core.executor"
    assert module_name(_bad("core", "wallclock_bad.py")) \
        == "repro.core.wallclock_bad"
    assert module_name("src/repro/memory/__init__.py") == "repro.memory"
    assert module_name("benchmarks/run.py") == ""


def test_wallclock_fixture_fires_and_twin_passes():
    assert _checks_of(_bad("core", "wallclock_bad.py")) \
        == ["wallclock", "wallclock", "wallclock"]
    assert _checks_of(_good("core", "wallclock_good.py")) == []


def test_setiter_fixture_fires_and_twin_passes():
    assert _checks_of(_bad("core", "setiter_bad.py")) \
        == ["wallclock", "wallclock"]
    assert _checks_of(_good("core", "setiter_good.py")) == []


def test_epoch_part_a_fixture_fires_and_twin_passes():
    viols = run_checks([_bad("memory", "residency.py")]).violations
    assert [v.check for v in viols] == ["epoch"]
    assert "DevicePool.add" in viols[0].message
    assert _checks_of(_good("memory", "residency.py")) == []


def test_epoch_part_b_fixture_fires_and_twin_passes():
    assert _checks_of(_bad("memory", "epoch_bad.py")) == ["epoch", "epoch"]
    assert _checks_of(_good("memory", "epoch_good.py")) == []


def test_tracer_fixture_fires_and_twin_passes():
    viols = run_checks([_bad("core", "tracer_bad.py")]).violations
    assert [v.check for v in viols] == ["tracer", "tracer"]
    assert "banana" in viols[1].message
    assert _checks_of(_good("core", "tracer_good.py")) == []


def test_frozenspec_fixture_fires_and_twin_passes():
    assert sorted(_checks_of(_bad("api", "frozenspec_bad.py"))) \
        == ["frozenspec", "frozenspec"]
    assert _checks_of(_good("api", "frozenspec_good.py")) == []


def test_docstring_fixture_fires_and_twin_passes():
    assert _checks_of(_bad("memory", "nodoc_bad.py")) \
        == ["epoch", "docstring"] or \
        _checks_of(_bad("memory", "nodoc_bad.py")) == ["docstring"]
    assert _checks_of(_good("memory", "nodoc_good.py")) == []


def test_allowlist_exemptions_honored():
    # simulator and serving read perf_counter for wall_s / sched_time —
    # declared measurement sites, so the wallclock check stays silent
    rep = run_checks([_os.path.join(ROOT, "src", "repro", "core",
                                    "simulator.py"),
                      _os.path.join(ROOT, "src", "repro", "core",
                                    "serving.py")], ("wallclock",))
    assert rep.violations == []


def test_real_tree_is_clean_and_strict():
    rep = run_checks([_os.path.join(ROOT, "src")])
    assert rep.violations == [], [v.render() for v in rep.violations]
    assert rep.warnings == [], [w.render() for w in rep.warnings]


def test_cli_exit_codes():
    env = dict(_os.environ, PYTHONPATH=_os.path.join(ROOT, "src"))
    bad = subprocess.run([sys.executable, "-m", "repro.analysis", BAD],
                         cwd=ROOT, env=env, capture_output=True)
    assert bad.returncode == 1, bad.stdout
    good = subprocess.run([sys.executable, "-m", "repro.analysis", GOOD],
                          cwd=ROOT, env=env, capture_output=True)
    assert good.returncode == 0, good.stdout


# --------------------------------------------------------------------------- #
# cachesan: silent on a clean run, raises on a corrupted cache entry,
# detects the injected stale-epoch fault, and installs from env/spec
# --------------------------------------------------------------------------- #
from repro.core import Simulation  # noqa: E402
from repro.core.workload import make_task_requests  # noqa: E402
from repro.memory import NUMA  # noqa: E402
from conftest import SMALL_BOARD, build_board_system  # noqa: E402

PEER = dataclasses.replace(NUMA, name="peer", peer_bw=300e9)


def test_cachesan_silent_on_clean_run():
    system = build_board_system(SMALL_BOARD, NUMA, n_gpu=2, n_cpu=1)
    san = CacheSanitizer(probe_rate=1.0, seed=0).install(system)
    sim = Simulation(system)
    sim.submit(make_task_requests(SMALL_BOARD, 120, interval=0.004, seed=0))
    m = sim.run()
    assert m.completed == 120
    assert san.probes > 100          # the caches were actually validated
    san.uninstall()


def test_cachesan_raises_on_corrupted_holders_cache():
    system = build_board_system(SMALL_BOARD, PEER, n_gpu=2, n_cpu=1)
    h = system.hierarchy
    assert h.topology.has_peer
    group = sorted(h.link_groups)[0]
    eid = sorted(system.coe.experts)[0]
    CacheSanitizer(probe_rate=1.0, seed=0).install(system)
    # a stale-epoch bug in miniature: a holders entry claiming a settled
    # sibling copy that no pool has (epoch stamp valid, value wrong)
    h._holders_cache[eid] = (h.epoch.n, ("phantom-pool",))
    with pytest.raises(CacheDivergence) as exc:
        h.assignment_cost(eid, 0.0, group)
    assert exc.value.epoch == h.epoch.n
    assert eid in str(exc.value)


def test_cachesan_raises_on_corrupted_work_cache():
    system = build_board_system(SMALL_BOARD, NUMA, n_gpu=2, n_cpu=1)
    ex = next(e for e in system.executors
              if e._residency_epoch() is not None)
    CacheSanitizer(probe_rate=1.0, seed=0).install(system)
    good = ex.queue_work()
    qv, en, _ = ex._work_cache
    ex._work_cache = (qv, en, good + 0.5)
    with pytest.raises(CacheDivergence):
        ex.queue_work()


def test_cachesan_self_test_detects_injected_fault():
    system = build_board_system(SMALL_BOARD, NUMA, n_gpu=2, n_cpu=1)
    assert sanitizer_self_test(system) is True
    # methods restored: a corrupted entry now goes undetected (no probes)
    assert getattr(system, "_cachesan", None) is None


def test_cachesan_env_var_installs(monkeypatch):
    monkeypatch.setenv("REPRO_CACHE_SANITIZE", "1")
    system = build_board_system(SMALL_BOARD, NUMA, n_gpu=2, n_cpu=1)
    assert getattr(system, "_cachesan", None) is not None
    monkeypatch.delenv("REPRO_CACHE_SANITIZE")
    system2 = build_board_system(SMALL_BOARD, NUMA, n_gpu=2, n_cpu=1)
    assert getattr(system2, "_cachesan", None) is None


def test_cachesan_spec_flag_installs():
    from repro.api import DeploymentSpec
    from repro.api.build import build_context
    spec = DeploymentSpec.load(_os.path.join(ROOT, "examples", "specs",
                                             "sim.json"))
    spec = dataclasses.replace(
        spec, observability=dataclasses.replace(spec.observability,
                                                sanitize=True))
    ctx = build_context(spec)
    assert getattr(ctx.system, "_cachesan", None) is not None


def test_cachesan_install_is_idempotent():
    system = build_board_system(SMALL_BOARD, NUMA, n_gpu=2, n_cpu=1)
    a = CacheSanitizer(probe_rate=0.5, seed=1).install(system)
    b = CacheSanitizer(probe_rate=0.9, seed=2).install(system)
    assert a is b and system._cachesan is a
