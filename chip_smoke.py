"""Smoke run of CoServe's served path on one TPU chip.

    python chip_smoke.py

One process; it starts no child process. The phases run in order, and the
first failed check raises, so the script exits non-zero:

1. device: JAX's default device must be a TPU. There is no CPU fallback.
2. coe: the CLI's real-engine path (``examples/specs/real.json`` with
   decode on) through ``repro.api.Session``. Every request completes, the
   pool switches experts, decode tokens are counted, every served label
   equals a NumPy evaluation of that expert's MLP, and the decode kernel
   lowers to a native TPU kernel.
3. lm: a catalog of full-width ``starcoder2_3b`` experts (bf16 weights made
   from a seed and kept in host memory, Pallas attention): two domain
   experts and a verifier that depends on both, one of them starting on the
   disk tier, and a device pool that holds two. It is served through
   ``CoServeSystem`` + ``RealEngine`` + ``run_real``. Every prompt
   completes, at least one switch happens, the served last-token logits
   match the ``attn_impl="xla"`` forward on the same weights and prompts,
   and the device's peak memory stays under its capacity.

Set-up and serve seconds are printed per phase; they are facts of this
smoke run, not benchmark numbers. The last line of stdout is
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}``.
The phase functions take their sizes, so tests drive them at tiny sizes on
the CPU; only ``main()`` requires the TPU.
"""
from __future__ import annotations

import dataclasses
import json
import sys
import tempfile
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.api import DeploymentSpec, Session  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import (COSERVE, CoEModel, CoServeSystem,  # noqa: E402
                        DeviceProfile, ExecutorSpec, ExpertSpec, HostStore,
                        RealEngine, Request, RoutingModule, TierSpec,
                        microbenchmark_arch, run_real)
from repro.core.engines import RingKVCache  # noqa: E402
from repro.kernels.ops import decode_attention_op, interpret_mode  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import transformer  # noqa: E402

REAL_SPEC = ROOT / "examples" / "specs" / "real.json"

# A served MLP label may differ from the NumPy one only where NumPy's two
# logits are this close: the device runs f32 matmuls in bf16 passes, whose
# error on these logits is ~0.006 (64- and 256-term dot products of
# O(0.1)-scaled weights), so a margin above 0.05 must give the same label.
LABEL_TIE = 0.05
# Served (Pallas attention) vs reference (XLA attention) last-token logits,
# both computed in bf16: the two attention paths round differently and the
# difference grows through the layers. The top logits of these random-init
# models lie in [2, 4), where a bf16 ulp is 2**-6; the bound is 16 ulps.
LOGIT_TOL = 0.25

DOMAINS = ("lm_code", "lm_math")
VERIFIER = "lm_verify"


class SmokeFailure(AssertionError):
    """A phase's check failed."""


def require(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_check():
    """Phase 1: the default device must be a TPU."""
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: JAX's default device is {dev.platform} "
            f"({dev.device_kind}), not a TPU; this smoke never falls back "
            "to the CPU")
    print(f"device: platform={dev.platform} kind={dev.device_kind} "
          f"count={len(jax.devices())}", flush=True)
    return dev


def _mlp_label(params, x):
    """The tiny expert's MLP in NumPy (float64): (label, logit margin)."""
    h = np.tanh(x.astype(np.float64) @ params["w1"] + params["b1"])
    out = h @ params["w2"] + params["b2"]
    return ("ok" if int(np.argmax(out)) == 0 else "defect",
            float(abs(out[0] - out[1])))


def decode_lowers_natively(engine: RealEngine) -> bool:
    """True when the decode kernel, at the geometry the engine's ring
    caches use, is dispatched natively and lowers to a TPU kernel."""
    ring = RingKVCache(**engine.decode_attn)
    q = jnp.zeros((1, ring.num_heads, ring.head_dim), ring.k.dtype)
    kv = jnp.asarray(ring.k)[None]
    lowered = jax.jit(
        lambda q, k, v, pos: decode_attention_op(q, k, v, pos,
                                                 window=ring.window)
    ).lower(q, kv, kv, 0)
    return not interpret_mode() and "tpu_custom_call" in lowered.as_text()


def coe_phase(requests: int = 60, decode_tokens: int = 0) -> dict:
    """Phase 2: the CLI's real-engine spec, decode on, served by Session.
    ``decode_tokens`` overrides the spec's decode length (0 keeps it)."""
    t0 = time.perf_counter()
    d = DeploymentSpec.load(str(REAL_SPEC)).to_dict()
    d["decode"]["enabled"] = True
    if decode_tokens:
        d["decode"]["tokens"] = decode_tokens
    d["workload"]["requests"] = requests
    sess = Session(DeploymentSpec.from_dict(d))
    engine = sess.system.engine

    served = []                   # (expert id, input, label) of every stage
    execute = engine.execute

    def recording_execute(ex, expert_id, batch):
        labels, lat = execute(ex, expert_id, batch)
        served.extend((expert_id, r.data["x"], label)
                      for r, label in zip(batch, labels))
        return labels, lat

    engine.execute = recording_execute
    setup_s = time.perf_counter() - t0
    t1 = time.perf_counter()
    out = sess.run()
    serve_s = time.perf_counter() - t1

    require(out["completed"] == requests,
            f"coe: completed {out['completed']} of {requests} requests")
    require(out["switches"] > 0, "coe: no expert switch")
    tokens = out.get("decode", {}).get("tokens_out", 0)
    require(tokens > 0, "coe: no decode tokens counted")
    ties = 0
    for expert_id, x, label in served:
        want, margin = _mlp_label(engine.store.fetch(expert_id)[0], x)
        if label != want:
            require(margin <= LABEL_TIE,
                    f"coe: {expert_id} served {label!r}, NumPy says "
                    f"{want!r} (logit margin {margin:.4f})")
            ties += 1
    return {"requests": requests, "completed": out["completed"],
            "switches": out["switches"], "decode_tokens": tokens,
            "labels_checked": len(served), "label_ties": ties,
            "decode_native": decode_lowers_natively(engine),
            "setup_s": setup_s, "serve_s": serve_s}


def _delete(params) -> None:
    for leaf in jax.tree.leaves(params):
        leaf.delete()


def _hbm_peak():
    """The default device's peak bytes in use so far (None where the
    backend keeps no memory statistics, as the CPU does)."""
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def lm_phase(cfg, prompts: int = 12, prompt_len: int = 64,
             seed: int = 0) -> dict:
    """Phase 3: a three-expert LM catalog of ``cfg`` through the real
    engine, with a two-expert device pool, checked against XLA attention."""
    t0 = time.perf_counter()
    cfg = dataclasses.replace(cfg, param_dtype="bfloat16",
                              attn_impl="pallas", remat=False)
    ref_cfg = dataclasses.replace(cfg, attn_impl="xla")

    def last_logits(c):
        @jax.jit
        def fn(params, tokens):
            logits, _ = transformer.forward(params, tokens, c, mode="eval")
            return logits[:, -1].astype(jnp.float32)
        return fn

    serve_fn, ref_fn = last_logits(cfg), last_logits(ref_cfg)
    mem = sum(leaf.size * leaf.dtype.itemsize
              for leaf in jax.tree.leaves(transformer.abstract_params(cfg)))
    ids = DOMAINS + (VERIFIER,)
    # jitted because, run eagerly, init keeps every layer's arrays and their
    # stacked copies on the device at once, far above one expert's bytes
    init = jax.jit(transformer.init_params, static_argnums=1)

    with tempfile.TemporaryDirectory(prefix="coserve_smoke_") as root:
        # weights: made from the seed on the device, one expert at a time,
        # and kept in host memory (the store holds NumPy arrays)
        store = HostStore(root=root)
        for i, expert_id in enumerate(ids):
            params = jax.device_get(init(jax.random.PRNGKey(seed + i), cfg))
            put = store.put_disk if expert_id == DOMAINS[1] else \
                store.put_host
            put(expert_id, params)
            del params
        peak_init = _hbm_peak()

        payload = {
            "make_batch": lambda reqs: np.stack(
                [r.data["tokens"] for r in reqs]),
            "interpret": lambda out: list(out),   # last-token logit rows
        }
        experts = [ExpertSpec(id=e, arch="lm", mem_bytes=mem,
                              payload=payload, usage_prob=0.5)
                   for e in DOMAINS]
        experts.append(ExpertSpec(id=VERIFIER, arch="lm", mem_bytes=mem,
                                  payload=payload, depends_on=DOMAINS,
                                  usage_prob=1.0))
        served = {}                # (prompt id, expert id) -> logit row

        def next_expert(req, expert_id, out):
            prompt = req.parent_id if expert_id == VERIFIER else req.id
            served[prompt, expert_id] = np.asarray(out)
            return None if expert_id == VERIFIER else VERIFIER

        coe = CoEModel(experts, RoutingModule(
            first_expert_fn=lambda data: data["domain"],
            next_expert_fn=next_expert,
            chain_prob={e: {VERIFIER: 1.0} for e in DOMAINS}))

        # offline profile with the served runner: compiles every batch
        # bucket the executor can form (max batch <= 8) before serving
        sample = jax.device_put(store.fetch(DOMAINS[0])[0])

        def run_batch(n: int) -> float:
            x = np.zeros((n, prompt_len), np.int32)
            jax.block_until_ready(serve_fn(sample, x))
            t = time.perf_counter()
            jax.block_until_ready(serve_fn(sample, x))
            return time.perf_counter() - t

        tier = TierSpec(name="lm", unified=True, host_cache_bytes=0,
                        device_bytes=3 * mem)
        prof = microbenchmark_arch("lm", run_batch, mem, prompt_len * 4,
                                   tier, batch_sizes=(1, 2, 4, 8),
                                   repeats=2)
        _delete(sample)
        del sample

        engine = RealEngine(coe, store, {"lm": serve_fn})
        dev_prof = DeviceProfile("tpu", tier, {"lm": prof})
        system = CoServeSystem(
            coe, [ExecutorSpec("tpu", dev_prof, 8 * prompt_len * 4, "tpu")],
            {"tpu": 2 * mem}, policy=COSERVE, tier=tier, engine=engine)
        rng = np.random.default_rng(seed)
        reqs = [Request(id=i, expert_id=DOMAINS[i % 2],
                        data={"domain": DOMAINS[i % 2],
                              "tokens": rng.integers(0, cfg.vocab_size,
                                                     prompt_len,
                                                     dtype=np.int32)})
                for i in range(prompts)]
        setup_s = time.perf_counter() - t0
        peak_setup = _hbm_peak()

        t1 = time.perf_counter()
        m = run_real(system, reqs)
        serve_s = time.perf_counter() - t1
        peak_serve = _hbm_peak()
        require(m.completed == prompts,
                f"lm: completed {m.completed} of {prompts} prompts")
        require(m.switches >= 1, "lm: no expert switch")

        # reference: the same forward with XLA attention, one expert on
        # the device at a time
        for expert_id in list(engine.device_params):
            engine.unload(None, expert_id)
        tokens = np.stack([r.data["tokens"] for r in reqs])
        ref = {}
        for expert_id in ids:
            params = jax.device_put(store.fetch(expert_id)[0])
            ref[expert_id] = np.asarray(ref_fn(params, tokens))
            _delete(params)

    same_token = 0
    max_diff = 0.0
    for r in reqs:
        for expert_id in (r.data["domain"], VERIFIER):
            got, want = served[r.id, expert_id], ref[expert_id][r.id]
            diff = float(np.max(np.abs(got - want)))
            max_diff = max(max_diff, diff)
            require(diff <= LOGIT_TOL,
                    f"lm: prompt {r.id} on {expert_id}: served logits "
                    f"differ from the xla reference by {diff:.4f}")
            tok = int(np.argmax(got))
            if tok == int(np.argmax(want)):
                same_token += 1
            else:
                require(want.max() - want[tok] <= LOGIT_TOL,
                        f"lm: prompt {r.id} on {expert_id}: served token "
                        f"{tok} is not the reference's (near-)argmax")
    return {"model": cfg.name, "prompts": prompts, "prompt_len": prompt_len,
            "completed": m.completed, "switches": m.switches,
            "expert_bytes": mem, "stages_checked": 2 * prompts,
            "same_next_token": same_token, "max_logit_diff": max_diff,
            "peak_bytes_after_init": peak_init,
            "peak_bytes_after_setup": peak_setup,
            "peak_bytes_after_serve": peak_serve,
            "setup_s": setup_s, "serve_s": serve_s}


def main() -> None:
    dev = device_check()
    enable_compile_cache()
    coe = coe_phase(requests=60)
    print("coe: " + json.dumps(coe), flush=True)
    require(coe["decode_native"],
            "coe: the decode kernel did not lower to a native TPU kernel")
    lm = lm_phase(get_config("starcoder2_3b"), prompts=12, prompt_len=64)
    print("lm: " + json.dumps(lm), flush=True)
    stats = dev.memory_stats() or {}
    peak, limit = stats.get("peak_bytes_in_use"), stats.get("bytes_limit")
    print(f"memory: peak_bytes_in_use={peak} bytes_limit={limit}",
          flush=True)
    require(peak is not None and limit is not None and peak < limit,
            "device peak memory missing or not under the chip's capacity")
    print(json.dumps({"ok": True,
                      "device": {"platform": dev.platform,
                                 "kind": dev.device_kind,
                                 "count": len(jax.devices())}}))


if __name__ == "__main__":
    main()
