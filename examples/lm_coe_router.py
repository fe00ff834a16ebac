"""LM Collaboration-of-Experts (the paper's §2.1 Qihoo-360 scenario): a
domain router dispatches prompts to specialised LM experts — real tiny
transformer checkpoints served through CoServe with actual device loads.

Chained dependency: every draft expert's output is verified by a shared
"safety" expert (the CoE dependency CoServe exploits).

  PYTHONPATH=src python examples/lm_coe_router.py
"""
import dataclasses
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, smoke_config
from repro.core import (COSERVE, SAMBA_PARALLEL, CoEModel, CoServeSystem,
                        DeviceProfile, ExecutorSpec, ExpertSpec, HostStore,
                        RealEngine, Request, RoutingModule, TierSpec,
                        microbenchmark_arch, run_real)
from repro.models import transformer

DOMAINS = ["code", "math", "law", "chat", "bio", "finance"]
N_REQS = 90

cfg = dataclasses.replace(smoke_config(get_config("starcoder2_3b")),
                          remat=False)


@jax.jit
def lm_apply(params, tokens):
    logits, _ = transformer.forward(params, tokens, cfg, mode="eval")
    return jnp.argmax(logits[:, -1], -1)          # next-token per prompt


def main():
    store = HostStore(root=tempfile.mkdtemp(prefix="lm_coe_"))
    payload = {
        "make_batch": lambda reqs: np.stack([r.data["tokens"] for r in reqs]),
        "interpret": lambda out: ["ok" if int(t) % 7 else "flag" for t in out],
    }
    mem = sum(p.size * p.dtype.itemsize
              for p in jax.tree.leaves(transformer.abstract_params(cfg)))

    # the store copies each expert's weights to host memory (NumPy)
    experts = []
    for i, dom in enumerate(DOMAINS):           # one fine-tune per domain
        params = transformer.init_params(jax.random.PRNGKey(i), cfg)
        (store.put_disk if i % 2 else store.put_host)(f"lm_{dom}", params)
        experts.append(ExpertSpec(
            id=f"lm_{dom}", arch="tiny_lm", mem_bytes=mem, payload=payload,
            usage_prob=1.0 / len(DOMAINS)))
    safety = transformer.init_params(jax.random.PRNGKey(99), cfg)
    store.put_disk("lm_safety", safety)
    experts.append(ExpertSpec(
        id="lm_safety", arch="tiny_lm", mem_bytes=mem, payload=payload,
        depends_on=tuple(f"lm_{d}" for d in DOMAINS), usage_prob=0.9))

    routing = RoutingModule(
        first_expert_fn=lambda data: f"lm_{data['domain']}",
        next_expert_fn=lambda req, eid, out: (
            "lm_safety" if eid != "lm_safety" else None),
        chain_prob={f"lm_{d}": {"lm_safety": 1.0} for d in DOMAINS})
    coe = CoEModel(experts, routing)
    engine = RealEngine(coe, store, {"tiny_lm": lm_apply})

    # offline profiling (paper §4.5) with the real jitted runner
    sample = transformer.init_params(jax.random.PRNGKey(7), cfg)

    def run_batch(n):
        x = np.zeros((n, 16), np.int32)
        lm_apply(sample, x)
        t0 = time.perf_counter()
        jax.block_until_ready(lm_apply(sample, x))
        return time.perf_counter() - t0

    tier = TierSpec(name="lm", unified=True, host_cache_bytes=0,
                    device_bytes=4 * mem)
    prof = microbenchmark_arch("tiny_lm", run_batch, mem, 16 * 4, tier,
                               batch_sizes=(1, 2, 4, 8), repeats=2)
    dev = DeviceProfile("gpu", tier, {"tiny_lm": prof})

    rng = np.random.RandomState(0)
    def requests():
        out = []
        for i in range(N_REQS):
            dom = DOMAINS[rng.randint(len(DOMAINS))]
            out.append(Request(
                id=i, expert_id=f"lm_{dom}",
                data={"domain": dom,
                      "tokens": rng.randint(0, cfg.vocab_size,
                                            16).astype(np.int32)}))
        return out

    for policy in (COSERVE, SAMBA_PARALLEL):
        system = CoServeSystem(
            coe, [ExecutorSpec("gpu", dev, 2 * mem, "gpu")] * 2,
            {"gpu": 3 * mem},                    # pool: 3 of 7 LM experts fit
            policy=policy, tier=tier, engine=RealEngine(
                coe, store, {"tiny_lm": lm_apply}))
        m = run_real(system, requests())
        print(f"{policy.name:18s}: {m.completed} prompts | "
              f"{m.switches:3d} expert loads | makespan {m.makespan:.2f}s")


if __name__ == "__main__":
    main()
