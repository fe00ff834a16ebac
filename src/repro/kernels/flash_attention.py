"""Flash attention (causal / GQA / sliding-window) as a Pallas TPU kernel.

Tiling (``flash_attention_plan`` chooses it from the static shapes and dtype;
read it there): the ``g = H / Hkv`` query heads of a KV group are folded into
one program, whose query tile is ``[g * block_q, head_dim]``, so the MXU sees
``g * block_q`` rows and each K/V tile is fetched once per group. A program
holds a chunk of the group's K/V in VMEM (the whole sequence when it fits)
and loops over its ``block_k`` blocks inside the kernel, only up to the
causal and window bounds: no tile above the diagonal or outside the window
is scheduled or fetched. Online softmax carries (m, l, acc) in VMEM scratch.
Only tiles that straddle the diagonal, the window's edge or the padded tail
of the keys build a mask.

Grid: (batch * kv_heads, q_blocks, kv_chunks); the kv-chunk axis is the
sequential one ("arbitrary") and has one step whenever the keys fit VMEM.
Operands reach the MXU in the input dtype with float32 accumulation; the
softmax runs in float32 and p is cast to v's dtype for p·v.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
SUBLANES = 16          # row multiple that keeps bf16 (and f32) tiles whole
LANES = 128
BLOCK_K = 512              # keys per inner step: amortizes the row stats
MAX_BLOCK_Q = 512
SCORE_TILE = 1536 * 512    # f32 scores per inner step that fit VMEM
KV_VMEM_BYTES = 4 << 20    # K and V chunks, double-buffered


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclasses.dataclass(frozen=True)
class FlashPlan:
    """How one call is split: blocks, head fold, padding and grid."""
    seq_q: int
    seq_k: int
    group: int          # query heads folded into one program
    block_q: int        # query positions per program (per head)
    block_k: int        # key positions per inner-loop step
    kv_chunk: int       # key positions held in VMEM per grid step
    causal: bool
    window: int

    @property
    def s_pad(self) -> int:
        return _round_up(self.seq_q, self.block_q)

    @property
    def t_pad(self) -> int:
        return _round_up(self.seq_k, self.kv_chunk)

    @property
    def n_q(self) -> int:
        return self.s_pad // self.block_q

    @property
    def n_chunks(self) -> int:
        return self.t_pad // self.kv_chunk

    @property
    def chunk_blocks(self) -> int:
        return self.kv_chunk // self.block_k

    def grid(self, batch: int, kv_heads: int) -> tuple[int, int, int]:
        return (batch * kv_heads, self.n_q, self.n_chunks)

    def kv_blocks(self, qi):
        """(lo, a, b, hi) over the global kv blocks of query block ``qi``:
        [lo, hi) is live, and of it only [a, b) needs no mask. Works on
        Python ints and on traced int32 scalars alike."""
        bq, bk = self.block_q, self.block_k
        first_q = qi * bq + (self.seq_k - self.seq_q)   # right-aligned
        last_q = first_q + bq - 1
        lo, hi = 0, -(-self.seq_k // bk)                # no all-padding block
        a, b = 0, self.seq_k // bk                      # no padded key
        if self.causal:
            hi = jnp.minimum(hi, last_q // bk + 1)
            b = jnp.minimum(b, (first_q + 1) // bk)
        if self.window:
            lo = jnp.maximum(first_q - self.window + 1, 0) // bk
            a = jnp.maximum(last_q - self.window + bk, 0) // bk
        a = jnp.clip(a, lo, hi)
        return lo, a, jnp.clip(b, a, hi), hi

    def tiles(self):
        """Every (q block, kv block, masked) tile one (batch, kv head)
        program row computes, in order: what the kernel schedules."""
        for qi in range(self.n_q):
            lo, a, b, hi = (int(x) for x in self.kv_blocks(qi))
            for kj in range(lo, hi):
                yield qi, kj, not a <= kj < b


def flash_attention_plan(s: int, t: int, g: int, d: int, dtype, *,
                         causal: bool = True, window: int = 0,
                         block_q: int | None = None,
                         block_k: int | None = None) -> FlashPlan:
    """The kernel's tiling for q ``[., g*Hkv, s, d]`` against k/v
    ``[., Hkv, t, d]``. Explicit blocks are kept (rounded to whole tiles).
    Otherwise ``block_k`` is ``BLOCK_K`` keys and the query block is the
    largest power of two up to ``MAX_BLOCK_Q`` whose score tile
    ``[g * block_q, block_k]`` stays within ``SCORE_TILE``. K/V stay whole in
    VMEM when they fit. A window that cannot bite at these shapes is 0."""
    block_k = _round_up(min(block_k or BLOCK_K, t), SUBLANES)
    if block_q is None:
        block_q = MAX_BLOCK_Q
        while g * block_q * block_k > SCORE_TILE and block_q > SUBLANES:
            block_q //= 2
    block_q = _round_up(min(block_q, s), SUBLANES)
    if window > t - s + _round_up(s, block_q) - 1:
        window = 0
    per_key = 2 * 2 * d * jnp.dtype(dtype).itemsize    # K and V, two buffers
    fit = max(KV_VMEM_BYTES // per_key // block_k, 1) * block_k
    kv_chunk = min(_round_up(t, block_k), fit)
    return FlashPlan(seq_q=s, seq_k=t, group=g, block_q=block_q,
                     block_k=block_k, kv_chunk=kv_chunk, causal=causal,
                     window=window)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               plan: FlashPlan, scale: float):
    qi, c = pl.program_id(1), pl.program_id(2)
    g, bq, bk = plan.group, plan.block_q, plan.block_k
    rows, d, lanes = g * bq, q_ref.shape[-1], l_ref.shape[-1]

    @pl.when(c == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    first_q = qi * bq + (plan.seq_k - plan.seq_q)
    base = c * plan.chunk_blocks                  # this chunk's first block

    def step(kj, masked):
        start = pl.multiple_of((kj - base) * bk, bk)
        k = k_ref[0, pl.ds(start, bk), :]
        v = v_ref[0, pl.ds(start, bk), :]
        q = q_ref[0].reshape(rows, d)             # g heads x block_q rows
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if masked:
            q_pos = first_q + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
            k_pos = kj * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
            mask = jnp.ones((bq, bk), bool)
            if plan.seq_k % bk:                   # a block with padded keys
                mask &= k_pos < plan.seq_k
            if plan.causal:
                mask &= q_pos >= k_pos
            if plan.window:
                mask &= (q_pos - k_pos) < plan.window
            s = jnp.where(mask[None], s.reshape(g, bq, bk),
                          NEG_INF).reshape(rows, bk)
        m_prev, l_prev = m_ref[...], l_ref[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.exp(s - m_new)
        # l stays per lane; its one cross-lane sum waits for _finalize
        l_ref[...] = l_prev * alpha + sum(
            p[:, i:i + lanes] for i in range(0, bk, lanes))
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    def loop(start, stop, masked):
        def body(kj, carry):
            step(kj, masked)
            return carry
        jax.lax.fori_loop(start, stop, body, 0)

    # the live blocks of this chunk: masked edges around an unmasked middle
    lo, a, b, hi = plan.kv_blocks(qi)
    lo = jnp.maximum(lo, base)
    hi = jnp.minimum(hi, base + plan.chunk_blocks)
    a, b = jnp.clip(a, lo, hi), jnp.clip(b, lo, hi)
    loop(lo, a, True)
    loop(a, b, False)
    loop(b, hi, True)

    @pl.when(c == plan.n_chunks - 1)
    def _finalize():
        l = l_ref[...].sum(axis=1, keepdims=True)
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)
                    ).reshape(g, bq, d).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "block_q", "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool):
    """q: [B,H,S,D]; k,v: [B,Hkv,T,D] -> [B,H,S,D] (GQA via head grouping).

    ``block_q``/``block_k`` override the plan's blocks (None: the plan's)."""
    b, h, s, d = q.shape
    hkv, t = k.shape[1], k.shape[2]
    g = h // hkv
    plan = flash_attention_plan(s, t, g, d, q.dtype, causal=causal,
                                window=window, block_q=block_q,
                                block_k=block_k)
    bq, chunk = plan.block_q, plan.kv_chunk

    # pad to block multiples (zero-fill; padded keys are masked by k_pos)
    if plan.s_pad > s:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, plan.s_pad - s), (0, 0)))
    if plan.t_pad > t:
        pad = ((0, 0), (0, 0), (0, plan.t_pad - t), (0, 0))
        k, v = jnp.pad(k, pad), jnp.pad(v, pad)

    # heads of a KV group are adjacent: program row r = bi*Hkv + kv head
    qf = q.reshape(b * hkv, g, plan.s_pad, d)
    kf = k.reshape(b * hkv, plan.t_pad, d)
    vf = v.reshape(b * hkv, plan.t_pad, d)

    def kv_index(r, qi, c):
        if plan.n_chunks == 1:
            return (r, 0, 0)
        # a chunk with no live block keeps the last one (no new copy)
        lo, _, _, hi = plan.kv_blocks(qi)
        per = plan.chunk_blocks
        return (r, jnp.clip(c, lo // per, (hi - 1) // per), 0)

    q_spec = pl.BlockSpec((1, g, bq, d), lambda r, qi, c: (r, 0, qi, 0))
    kv_spec = pl.BlockSpec((1, chunk, d), kv_index)
    rows = g * bq
    lanes = LANES if plan.block_k % LANES == 0 else plan.block_k
    out = pl.pallas_call(
        functools.partial(_fa_kernel, plan=plan, scale=d ** -0.5),
        grid=plan.grid(b, hkv),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(qf.shape, q.dtype),
        scratch_shapes=[
            pltpu.VMEM((rows, 1), jnp.float32),       # running max m
            pltpu.VMEM((rows, lanes), jnp.float32),   # running sum l
            pltpu.VMEM((rows, d), jnp.float32),       # output accumulator
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="flash_attention",  # the op's name in HLO and device traces
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, plan.s_pad, d)[:, :, :s]
