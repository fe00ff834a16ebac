"""Launch-layer tests: real-engine serving and elastic autoscaling."""
import numpy as np
import pytest

from repro.core import COSERVE, CoServeSystem, Request, Simulation, TierSpec
from repro.core.workload import (BoardSpec, build_board_coe,
                                 make_executor_specs, make_task_requests)
from repro.launch.elastic import ElasticController, ElasticPolicy


# --------------------------------------------------------------------------- #
# real-engine serving (actual JAX experts across host/disk tiers)
# --------------------------------------------------------------------------- #

@pytest.fixture(scope="module")
def real_system():
    from repro.launch.serve import build_real_system
    return build_real_system(n_components=12, n_detection=2, pool_experts=4,
                             n_executors=2)


def test_real_engine_completes_all(real_system):
    from repro.core import run_real
    system, coe = real_system
    rng = np.random.RandomState(0)
    reqs = [Request(id=i, expert_id=f"cls{rng.randint(12):03d}",
                    data={"component": 0, "x": rng.randn(64).astype(np.float32),
                          "needs_detection": False, "det_expert": 0})
            for i in range(60)]
    m = run_real(system, reqs)
    assert m.completed == 60
    assert m.switches > 0          # the pool is smaller than the expert set
    assert all(r.result in ("ok", "defect") for r in reqs)


def test_real_engine_coserve_switches_less_than_fcfs():
    """Through the REAL execution path too, dependency-aware scheduling +
    eviction must cut expert switches vs the Samba-style FCFS+LRU baseline
    (wall-clock jitter shifts event order run-to-run, so we compare policies,
    not exact counts)."""
    from repro.core import SAMBA_PARALLEL, run_real
    from repro.launch.serve import build_real_system

    def run(policy):
        system, _ = build_real_system(n_components=12, n_detection=2,
                                      pool_experts=4, n_executors=2,
                                      policy=policy)
        rng = np.random.RandomState(3)
        reqs = [Request(id=i, expert_id=f"cls{rng.randint(12):03d}",
                        data={"component": 0,
                              "x": rng.randn(64).astype(np.float32),
                              "needs_detection": False, "det_expert": 0})
                for i in range(80)]
        return run_real(system, reqs)

    co, fcfs = run(COSERVE), run(SAMBA_PARALLEL)
    assert co.completed == fcfs.completed == 80
    assert co.switches < fcfs.switches


# --------------------------------------------------------------------------- #
# elastic autoscaling
# --------------------------------------------------------------------------- #

BOARD = BoardSpec(name="T", n_components=60, n_active=36, n_detection=8)
TIER = TierSpec(name="t", unified=False, host_cache_bytes=2 << 30,
                device_bytes=4 << 30)


def _system(n_gpu):
    coe = build_board_coe(BOARD)
    pools, specs = make_executor_specs(TIER, n_gpu, 0)
    return CoServeSystem(coe, specs, pools, policy=COSERVE, tier=TIER), specs


def test_elastic_scales_up_under_load():
    system, specs = _system(1)
    ctl = ElasticController(system, specs[0],
                            ElasticPolicy(max_executors=4,
                                          scale_up_pending_s=0.5))
    sim = Simulation(system)
    sim.submit(make_task_requests(BOARD, 500, interval=0.001))  # burst
    ctl.install(sim, horizon_s=30.0)
    m = sim.run()
    assert m.completed == 500
    assert any(a["action"] == "add" for a in ctl.actions), "never scaled up"


def test_elastic_drain_loses_nothing():
    system, specs = _system(3)
    ctl = ElasticController(system, specs[0],
                            ElasticPolicy(min_executors=1,
                                          scale_down_pending_s=10.0,
                                          scale_up_pending_s=1e9))
    sim = Simulation(system)
    sim.submit(make_task_requests(BOARD, 300))
    ctl.install(sim, horizon_s=5.0)   # aggressive shrink while work remains
    m = sim.run()
    assert m.completed == 300
    assert any(a["action"] == "remove" for a in ctl.actions)


def test_elastic_respects_bounds():
    system, specs = _system(2)
    pol = ElasticPolicy(min_executors=2, max_executors=3,
                        scale_up_pending_s=0.1, scale_down_pending_s=0.0)
    ctl = ElasticController(system, specs[0], pol)
    sim = Simulation(system)
    sim.submit(make_task_requests(BOARD, 400, interval=0.001))
    ctl.install(sim, horizon_s=20.0)
    sim.run()
    assert len(system.live_executors()) <= pol.max_executors
    assert len(system.live_executors()) >= pol.min_executors
