"""What running the served path on one TPU chip depends on, checked on the
CPU: the host tier is host memory, eviction frees device buffers, kernel
dispatch picks interpret mode off the TPU, the compile cache sits at one
fixed place, and ``chip_smoke.py``'s phases pass at tiny sizes."""
from __future__ import annotations

import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def chip_smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke",
                                                  ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def tiny_real():
    from repro.api.build import build_real_system
    return build_real_system(n_components=6, n_detection=2, pool_experts=3,
                             n_executors=1)


# --------------------------------------------------------------------------- #
# host tier = host memory
# --------------------------------------------------------------------------- #

def test_build_real_system_host_tier_holds_no_device_arrays(tiny_real):
    system, _ = tiny_real
    store = system.engine.store
    assert store.host
    leaves = jax.tree.leaves(store.host)
    assert leaves and not any(isinstance(a, jax.Array) for a in leaves)
    assert all(isinstance(a, np.ndarray) for a in leaves)


def test_build_real_system_without_cpu_backend(monkeypatch):
    """Only the CPU service-time profile asks for the CPU backend: without
    one (JAX_PLATFORMS=tpu) the system still builds, serving on the default
    device, and its profile carries no CPU line."""
    from repro.api.build import build_real_system
    devices = jax.devices

    def no_cpu(backend=None):
        if backend == "cpu":
            raise RuntimeError("Unknown backend: 'cpu' requested")
        return devices(backend)

    monkeypatch.setattr(jax, "devices", no_cpu)
    system, _ = build_real_system(n_components=4, n_detection=1,
                                  pool_experts=2, n_executors=1)
    prof = system.executors[0].profile("tiny_cls")
    assert prof.cpu_k == 0.0 and prof.cpu_b == 0.0 and prof.k != 0.0


@pytest.mark.parametrize("tier", ["host", "disk"])
def test_host_store_keeps_numpy_with_dtypes(tmp_path, tier):
    from repro.core.engines import HostStore
    store = HostStore(root=str(tmp_path))
    params = {"w": jnp.arange(6, dtype=jnp.bfloat16).reshape(2, 3),
              "b": jnp.ones((3,), jnp.float32)}
    (store.put_host if tier == "host" else store.put_disk)("e", params)
    got, source = store.fetch("e")
    assert source == tier
    for name in params:
        assert isinstance(got[name], np.ndarray)
        assert got[name].dtype == params[name].dtype
        np.testing.assert_array_equal(np.asarray(got[name], np.float32),
                                      np.asarray(params[name], np.float32))


# --------------------------------------------------------------------------- #
# eviction frees device buffers
# --------------------------------------------------------------------------- #

def test_real_engine_unload_deletes_evicted_buffers(tiny_real):
    system, coe = tiny_real
    engine = system.engine
    eid = next(e for e in coe.experts if e not in engine.device_params)
    engine.warm_place(None, eid)
    leaves = jax.tree.leaves(engine.device_params[eid])
    assert leaves and not any(a.is_deleted() for a in leaves)
    engine.unload(None, eid)
    assert eid not in engine.device_params
    assert all(a.is_deleted() for a in leaves)
    # the host copy is untouched: the expert can load again
    engine.warm_place(None, eid)
    assert not any(a.is_deleted()
                   for a in jax.tree.leaves(engine.device_params[eid]))
    engine.unload(None, eid)


def test_real_engine_unload_keeps_host_executed_params(tiny_real):
    system, coe = tiny_real
    engine = system.engine
    eid = next(e for e in engine.store.host if e not in engine.device_params)
    engine.device_params[eid] = engine.store.host[eid]  # host co-execution
    engine.unload(None, eid)
    assert eid in engine.store.host
    assert all(isinstance(a, np.ndarray)
               for a in jax.tree.leaves(engine.store.host[eid]))


# --------------------------------------------------------------------------- #
# kernel dispatch
# --------------------------------------------------------------------------- #

def test_kernel_dispatch_interprets_off_tpu():
    from repro.kernels import ops
    assert jax.default_backend() == "cpu"
    assert ops.interpret_mode() is True


def test_kernels_take_no_default_interpret_mode():
    from repro.kernels.decode_attention import decode_attention
    q = jnp.zeros((1, 4, 64), jnp.float32)
    kv = jnp.zeros((1, 2, 64, 64), jnp.float32)
    with pytest.raises(TypeError):
        decode_attention(q, kv, kv, 0)


def test_ring_kv_cache_attends_through_dispatch(monkeypatch):
    from repro.core.engines import RingKVCache
    from repro.kernels import ops
    from repro.kernels.decode_attention import decode_attention

    calls = []
    real_op = ops.decode_attention_op

    def spy(*args, **kwargs):
        calls.append(kwargs)
        return real_op(*args, **kwargs)

    monkeypatch.setattr(ops, "decode_attention_op", spy)
    rng = np.random.default_rng(0)
    cache = RingKVCache(num_heads=4, num_kv_heads=2, head_dim=64, width=64)
    for _ in range(3):
        cache.append(rng.standard_normal((2, 64)),
                     rng.standard_normal((2, 64)))
    q = rng.standard_normal((4, 64)).astype(np.float32)
    out = cache.attend(q)
    assert len(calls) == 1 and "interpret" not in calls[0]
    want = decode_attention(jnp.asarray(q)[None], jnp.asarray(cache.k)[None],
                            jnp.asarray(cache.v)[None], cache.pos,
                            interpret=True)
    np.testing.assert_array_equal(out, np.asarray(want[0]))


# --------------------------------------------------------------------------- #
# compile cache location
# --------------------------------------------------------------------------- #

@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_env(monkeypatch, restore_cache_dir):
    from repro.launch.compile_cache import enable_compile_cache
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert enable_compile_cache() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_fixed_in_checkout(monkeypatch,
                                                    restore_cache_dir):
    from repro.launch.compile_cache import enable_compile_cache
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert first == enable_compile_cache() == str(ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    ignored = (ROOT / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored


# --------------------------------------------------------------------------- #
# chip_smoke.py: same phase code at tiny sizes, and no CPU fallback
# --------------------------------------------------------------------------- #

def test_chip_smoke_refuses_cpu(chip_smoke, capsys):
    with pytest.raises(SystemExit) as e:
        chip_smoke.main()
    assert e.value.code not in (0, None)
    assert '"ok"' not in capsys.readouterr().out


def test_chip_smoke_coe_phase_tiny(chip_smoke):
    out = chip_smoke.coe_phase(requests=12, decode_tokens=2)
    assert out["completed"] == 12 and out["switches"] > 0
    assert out["decode_tokens"] == 12 * 2
    assert out["labels_checked"] >= 12
    assert out["decode_native"] is False       # CPU: interpreted


def test_chip_smoke_lm_phase_tiny(chip_smoke):
    from repro.configs import get_config, smoke_config
    out = chip_smoke.lm_phase(smoke_config(get_config("starcoder2_3b")),
                              prompts=4, prompt_len=16)
    assert out["completed"] == 4 and out["switches"] >= 1
    assert out["stages_checked"] == 8
    assert out["max_logit_diff"] <= chip_smoke.LOGIT_TOL
