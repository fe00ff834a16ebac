"""Execution engines behind the executor state machine.

``SimEngine`` — latencies from offline profiles + the unified memory
hierarchy (``repro.memory``); drives the event-driven simulator at the
paper's scale (hundreds of experts) without touching a device. Every
transfer it performs occupies the hierarchy's *shared* SSD/PCIe channels, so
concurrent loads contend instead of each pretending it owns the link.

``RealEngine`` — actually loads JAX expert params across host/disk tiers and
runs jitted forwards, measuring wall time. Loads queue on real transfer
threads that mirror the tier topology: one thread per transfer channel
(one shared thread in ``links="shared"`` mode — the machine has one storage
link — or one per device pool in ``links="per-device"`` mode), so prefetch
genuinely overlaps host I/O with device compute and concurrent loads
serialize exactly where the simulated channels would. Scheduler and
expert-manager behaviour (and therefore switch counts) are
engine-independent.
"""
from __future__ import annotations

import os
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from repro.core.coe import CoEModel, Request
from repro.memory import MemoryHierarchy, TierSpec
from repro.obs import NULL_TRACER


class SimEngine:
    """Profiled-latency engine (paper-scale simulation)."""

    def __init__(self, coe: CoEModel, tier: Optional[TierSpec],
                 hierarchy: Optional[MemoryHierarchy] = None):
        self.coe = coe
        self.tier = tier
        # standalone construction (tests, notebooks): derive a hierarchy so
        # the latency model and channels always exist
        self.hierarchy = hierarchy if hierarchy is not None \
            else MemoryHierarchy(coe, tier, pools={})

    # --- latency model (uncontended predictions) ------------------------ #
    def load_latency(self, ex, expert_id: str) -> float:
        if ex is not None and ex.device in ("host", "cpu"):
            h = self.hierarchy
            if h.host_exec_enabled and h.in_host(expert_id):
                return 0.0             # host co-execution: runs in place
            return h.predict_host_load(expert_id)
        group = ex.link_group if ex is not None else ""
        return self.hierarchy.predict_device_load(expert_id, group)

    def exec_latency(self, ex, expert_id: str, n: int) -> float:
        prof = ex.profile(self.coe.spec(expert_id).arch)
        return prof.exec_latency(n)

    # --- side effects --------------------------------------------------- #
    def load(self, ex, expert_id: str, now: float = 0.0) -> float:
        """Begin the transfer on the contended channels; returns the latency
        the executor observes (queueing wait + service legs). The PCIe leg
        rides the executor's own device link in per-device mode."""
        if ex is not None and ex.device in ("host", "cpu"):
            tr = self.hierarchy.begin_host_load(expert_id, now)
        else:
            group = ex.link_group if ex is not None else ""
            tr = self.hierarchy.begin_device_load(expert_id, now, group=group)
        return tr.latency

    def unload(self, ex, expert_id: str) -> None:
        if ex is not None and ex.device in ("host", "cpu"):
            return                      # CPU pool lives in DRAM already
        self.hierarchy.note_evicted(expert_id)

    def execute(self, ex, expert_id: str, batch: List[Request]
                ) -> Tuple[Optional[list], float]:
        # outcome is carried by the synthetic request payload (drives routing)
        outputs = [None if r.data is None else r.data.get("outcome")
                   for r in batch]
        return outputs, self.exec_latency(ex, expert_id, len(batch))


class RingKVCache:
    """One request's ring KV cache for the real decode path.

    Host-side numpy rings in the heads-major layout ``slot_cache_shape``
    emits ([Hkv, W, D]); ``append`` writes slot ``pos % width`` (the ring
    update), ``attend`` runs the Pallas ``decode_attention`` kernel over
    the ring through ``decode_attention_op`` (native on TPU, interpreted
    on other backends). Positions past ``width`` overwrite the oldest slot
    — the kernel's validity mask reconstructs absolute positions from the
    scalar ``pos``.
    """

    def __init__(self, num_heads: int = 4, num_kv_heads: int = 2,
                 head_dim: int = 64, width: int = 64,
                 dtype: str = "float32", window: int = 0):
        self.num_heads = num_heads
        self.num_kv_heads = num_kv_heads
        self.head_dim = head_dim
        self.width = width
        self.window = window
        self.dtype = np.dtype(dtype) if dtype != "bfloat16" else dtype
        shape = (num_kv_heads, width, head_dim)
        if dtype == "bfloat16":
            import jax.numpy as jnp
            self.k = np.zeros(shape, jnp.bfloat16.dtype)
            self.v = np.zeros(shape, jnp.bfloat16.dtype)
        else:
            self.k = np.zeros(shape, self.dtype)
            self.v = np.zeros(shape, self.dtype)
        self.pos = -1                   # last written absolute position

    def append(self, k: np.ndarray, v: np.ndarray) -> int:
        """Write this step's [Hkv, D] k/v at the next ring slot; returns
        the absolute position written."""
        self.pos += 1
        slot = self.pos % self.width
        self.k[:, slot, :] = k.astype(self.k.dtype)
        self.v[:, slot, :] = v.astype(self.v.dtype)
        return self.pos

    def attend(self, q: np.ndarray):
        """[H, D] query against the ring -> [H, D] output (B=1 kernel
        call; members of one continuous batch have different ``pos`` so
        they cannot share a batched call)."""
        import jax.numpy as jnp

        from repro.kernels.ops import decode_attention_op
        out = decode_attention_op(
            jnp.asarray(q)[None], jnp.asarray(self.k)[None],
            jnp.asarray(self.v)[None], self.pos, window=self.window)
        return np.asarray(out[0])


class HostStore:
    """Host-DRAM + disk parameter store for the real backend.

    Experts start on 'disk' (.npz files) or in host memory; loads into an
    executor deserialize + ``jax.device_put`` the pytree — the real analogue
    of the paper's SSD -> DRAM -> GPU expert switching. The host tier holds
    NumPy arrays only: ``put_host`` copies device arrays to host memory, so
    a host -> device load is one transfer and the host tier never occupies
    device memory.
    """

    def __init__(self, root: Optional[str] = None):
        self.host: Dict[str, Any] = {}
        self.disk: Dict[str, str] = {}
        self.root = root
        self._disk_layout: Dict[str, Tuple[Any, list]] = {}

    def put_host(self, expert_id: str, params: Any):
        import jax
        self.host[expert_id] = jax.device_get(params)

    def put_disk(self, expert_id: str, params: Any):
        import jax
        assert self.root, "HostStore needs a root dir for disk tier"
        os.makedirs(self.root, exist_ok=True)
        path = os.path.join(self.root, f"{expert_id}.npz")
        leaves, treedef = jax.tree.flatten(jax.device_get(params))
        leaves = [np.asarray(l) for l in leaves]
        np.savez(path, *leaves)
        self.disk[expert_id] = path
        # .npz keeps no extension dtypes (bfloat16 reads back as raw bytes):
        # the dtypes are restored by view on fetch
        self._disk_layout[expert_id] = (treedef, [l.dtype for l in leaves])

    def fetch(self, expert_id: str) -> Tuple[Any, str]:
        """Returns (host-side params, source tier)."""
        import jax
        if expert_id in self.host:
            return self.host[expert_id], "host"
        path = self.disk[expert_id]
        treedef, dtypes = self._disk_layout[expert_id]
        with np.load(path) as z:
            leaves = [z[k].view(dt) for k, dt in zip(z.files, dtypes)]
        params = jax.tree.unflatten(treedef, leaves)
        self.host[expert_id] = params          # disk read populates host cache
        return params, "disk"


class _TransferWorker:
    """The real backend's single transfer channel: one daemon thread that
    performs fetch + device_put jobs FIFO. Concurrent loads from different
    executors serialize here — the real-hardware analogue of the simulator's
    contended ``TransferChannel``."""

    def __init__(self):
        self._q: "queue.Queue" = queue.Queue()
        self._thread: Optional[threading.Thread] = None

    def _ensure_started(self):
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(target=self._run, daemon=True,
                                            name="coserve-transfer")
            self._thread.start()

    def _run(self):
        while True:
            fn, done = self._q.get()
            try:
                fn()
            except BaseException as e:  # surfaced by wait()
                done["error"] = e
            finally:
                done["event"].set()
                self._q.task_done()

    def submit(self, fn) -> dict:
        self._ensure_started()
        done = {"event": threading.Event(), "error": None}
        self._q.put((fn, done))
        return done

    @staticmethod
    def wait(handle: dict):
        handle["event"].wait()
        if handle["error"] is not None:
            raise handle["error"]


class RealEngine:
    """Runs real JAX experts; latencies are measured wall time.

    ``apply_fns[arch]``: jitted fn (params, batch_array) -> outputs. Expert
    payloads supply ``make_batch(requests) -> array`` and
    ``interpret(outputs) -> list`` hooks via the CoE expert payload dict.

    Transfers ride per-channel transfer threads: ``load()`` enqueues on the
    thread of the link the executor's pool uses (``bind_topology`` maps pool
    group -> channel; unbound or shared-link mode keeps the seed's single
    thread) and returns the *predicted* latency (so scheduling stays
    deterministic), and the executor's ``finish_load`` blocks until the
    transfer really completed. ``measured_load_time`` accumulates the wall
    time the workers actually spent moving timed (post-init) loads; it is
    surfaced in ``Metrics.memory['real_measured_load_s']``.

    With the system's tracer's wall side on (``Tracer(wall=True)``, handed
    over by ``bind_topology``), each call also keeps ``coserve.*`` wall
    spans: ``execute`` and its phases, ``switch_wait``, ``transfer`` with
    its ``fetch`` and ``device_put``, and ``evict``.
    """

    def __init__(self, coe: CoEModel, store: HostStore, apply_fns: Dict[str, Any]):
        self.coe = coe
        self.store = store
        self.apply_fns = apply_fns
        self.device_params: Dict[str, Any] = {}
        self._workers: Dict[str, _TransferWorker] = {}
        self._topology = None
        self._hierarchy = None
        self._pending: Dict[str, dict] = {}
        # the scheduler's predicted seconds of each queued transfer, kept
        # beside the measured ones in the coserve.transfer span
        self._predicted: Dict[str, float] = {}
        self._lock = threading.Lock()
        self.measured_load_time = 0.0
        # heterogeneous CPU co-execution (policy.host_exec): host/CPU
        # executors run host-resident experts straight from the DRAM store —
        # no transfer thread, no deserialization round-trip
        self.host_exec_enabled = False
        # token-level decode (PR 9): one ring KV cache per mid-generation
        # request, driving the Pallas decode_attention kernel per step.
        # ``decode_attn`` overrides the cache geometry (heads/width/dtype).
        self.decode_caches: Dict[int, RingKVCache] = {}
        self.decode_attn: Dict[str, Any] = {}
        self.tracer = NULL_TRACER

    # --- topology binding (one transfer thread per transfer channel) ---- #
    def bind_topology(self, topology, hierarchy=None, tracer=None) -> None:
        """Mirror the tier topology's channels: each PCIe channel, peer
        ingress link (or the SSD link on unified tiers) gets its own FIFO
        transfer thread, so the real backend serializes loads exactly where
        the simulator's contended channels would. ``hierarchy`` (when given)
        lets loads of experts already resident on a sibling pool ride that
        pool's peer channel thread; ``tracer`` is the system's flight
        recorder, whose wall spans time this engine's calls. Called by
        ``CoServeSystem``."""
        self._topology = topology
        self._hierarchy = hierarchy
        if tracer is not None:
            self.tracer = tracer

    def _channel_name(self, ex, expert_id: str = "") -> str:
        if self._topology is None or ex is None:
            return ""                  # unbound: the seed's single thread
        t = self._topology
        if t.spec.unified or getattr(ex, "device", "") in ("host", "cpu"):
            # one storage link carries the load (host/CPU executors load
            # disk -> DRAM and never own a PCIe channel)
            return t.disk_channel.name
        if expert_id and self._hierarchy is not None \
                and self._hierarchy.peer_source(expert_id,
                                                ex.link_group) is not None:
            return t.peer_for(ex.link_group).name
        return t.pcie_for(ex.link_group).name

    def _worker_for(self, name: str) -> _TransferWorker:
        with self._lock:
            worker = self._workers.get(name)
            if worker is None:
                worker = self._workers[name] = _TransferWorker()
            return worker

    def _host_exec_hit(self, ex, expert_id: str) -> bool:
        return (self.host_exec_enabled and ex is not None
                and getattr(ex, "device", "") in ("host", "cpu")
                and expert_id in self.store.host)

    def load_latency(self, ex, expert_id: str) -> float:
        # prediction for scheduling: profiled value (derived from the
        # TransferEngine formula at profiling time)
        if self._host_exec_hit(ex, expert_id):
            return 0.0                 # host co-execution: runs in place
        spec = self.coe.spec(expert_id)
        prof = ex.profile(spec.arch)
        return prof.load_latency_host if expert_id in self.store.host \
            else prof.load_latency_disk

    def exec_latency(self, ex, expert_id: str, n: int) -> float:
        prof = ex.profile(self.coe.spec(expert_id).arch)
        return prof.exec_latency(n)

    # ------------------------------------------------------------------ #
    def _transfer(self, expert_id: str, timed: bool = True):
        import jax
        span = self.tracer.span
        predicted = self._predicted.pop(expert_id, None)
        with span("coserve.transfer", expert=expert_id,
                  bytes=self.coe.spec(expert_id).mem_bytes,
                  predicted_s=predicted, timed=timed) as sp:
            t0 = time.perf_counter()
            with span("coserve.transfer.fetch") as fetch:
                host_params, tier = self.store.fetch(expert_id)
                fetch.set(tier=tier)
            sp.set(tier=tier)
            with span("coserve.transfer.device_put") as put:
                dev = jax.block_until_ready(jax.device_put(host_params))
                if self.tracer.wall:
                    put.set(bytes=sum(leaf.nbytes
                                      for leaf in jax.tree.leaves(dev)),
                            timed=timed)
            with self._lock:
                self.device_params[expert_id] = dev
                if timed:
                    self.measured_load_time += time.perf_counter() - t0

    def load(self, ex, expert_id: str, now: float = 0.0) -> float:
        if self._host_exec_hit(ex, expert_id):
            # execute in place on the CPU: the host-store params ARE the
            # executable params — no worker round-trip, nothing pending
            with self._lock:
                self.device_params[expert_id] = self.store.host[expert_id]
            return 0.0
        predicted = self.load_latency(ex, expert_id)
        if self.tracer.wall:
            self._predicted[expert_id] = predicted
        worker = self._worker_for(self._channel_name(ex, expert_id))
        handle = worker.submit(lambda: self._transfer(expert_id))
        with self._lock:
            self._pending[expert_id] = handle
        return predicted

    def wait_load(self, ex, expert_id: str) -> None:
        """Block until the queued transfer landed (executor ``finish_load``)."""
        with self._lock:
            handle = self._pending.pop(expert_id, None)
        if handle is not None:
            with self.tracer.span("coserve.switch_wait", expert=expert_id):
                _TransferWorker.wait(handle)

    def unload(self, ex, expert_id: str) -> None:
        """Evict: delete the expert's device buffers now, so the incoming
        load lands in freed memory (the device peak is the pool plus the
        working set, never pool plus one expert). Host-executed params are
        the host store's NumPy arrays and stay."""
        import jax
        with self.tracer.span("coserve.evict", expert=expert_id):
            self.wait_load(ex, expert_id)  # never drop a half-landed transfer
            with self._lock:
                params = self.device_params.pop(expert_id, None)
            for leaf in jax.tree.leaves(params):
                if isinstance(leaf, jax.Array):
                    leaf.delete()

    def warm_place(self, pool, expert_id: str) -> None:
        """Initial placement (system-init phase): transfer without timing."""
        self._transfer(expert_id, timed=False)

    # --- token-level decode (PR 9) -------------------------------------- #
    def decode_step(self, ex, states, now: float = 0.0) -> float:
        """Run one decode step for every member of ``ex``'s continuous
        batch: append this step's k/v to each request's ring cache and run
        the Pallas decode kernel against it (B=1 per member — members sit
        at different ring positions). Inputs are hash-seeded per
        (request, position) so replays are deterministic. Returns measured
        wall seconds — the DecodeRuntime's step latency."""
        t0 = time.perf_counter()
        for st in states:
            rid = st.req.id
            cache = self.decode_caches.get(rid)
            if cache is None:
                cache = self.decode_caches[rid] = \
                    RingKVCache(**self.decode_attn)
            rng = np.random.default_rng(abs(hash((rid, cache.pos + 1)))
                                        % (2 ** 32))
            hkv, d = cache.num_kv_heads, cache.head_dim
            cache.append(rng.standard_normal((hkv, d)),
                         rng.standard_normal((hkv, d)))
            q = rng.standard_normal((cache.num_heads, d))
            st.req.result = cache.attend(q)
        return time.perf_counter() - t0

    def decode_release(self, rid: int) -> None:
        """A request finished (or was orphaned): drop its ring cache."""
        self.decode_caches.pop(rid, None)

    def execute(self, ex, expert_id: str, batch: List[Request]
                ) -> Tuple[list, float]:
        import jax
        spec = self.coe.spec(expert_id)
        payload = spec.payload or {}
        tracer = self.tracer
        span = tracer.span
        with span("coserve.execute", expert=expert_id,
                  rows=len(batch)) as step:
            t0 = time.perf_counter()
            params = self.device_params[expert_id]
            make_batch = payload["make_batch"]
            interpret = payload.get("interpret", lambda o: list(o))
            with span("coserve.execute.inputs"):
                x = make_batch(batch)
                # pad the batch dim to a power-of-two bucket: one XLA
                # compile per bucket instead of one per group size
                # (production bucketing)
                n = x.shape[0]
                bucket = 1 << (n - 1).bit_length()
                if bucket != n:
                    pad = np.zeros((bucket - n,) + x.shape[1:], x.dtype)
                    x = np.concatenate([x, pad], axis=0)
            if tracer.wall:
                step.set(bucket=bucket, requests=[r.id for r in batch])
            with span("coserve.execute.dispatch"):
                out = self.apply_fns[spec.arch](params, x)
            with span("coserve.execute.device_wait"):
                out = jax.block_until_ready(out)
            lat = time.perf_counter() - t0
            with span("coserve.execute.outputs") as outputs:
                host = np.asarray(out)
                outputs.set(bytes=host.nbytes)
                result = interpret(host[:n])
        return result, lat
