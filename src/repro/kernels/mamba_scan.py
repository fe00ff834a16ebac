"""Mamba-1 selective scan as a Pallas TPU kernel.

Layout (``mamba_scan_plan`` chooses the tiling from the static shapes; read
it there). The SSM state of a program is ``[N, block_d]`` f32 per batch row:
channels on the 128 lanes, the states on sublanes, so at N = 16 and
block_d = 512 it is 8 full vregs. A step of the recurrence

    h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t,   y_t = C_t . h_t

is full-width elementwise work on that tile:

- ``dt_t`` and ``u_t = dt_t x_t`` are rows ``[1, block_d]``, read with a
  sublane-broadcasting (stride-0) load from f32 copies of the chunk kept one
  128-lane group per page (``u`` is formed once per chunk, vectorised);
- ``A`` is held transposed, ``[N, block_d]``, scaled by log2(e) once per
  chunk, so ``exp(dt A)`` is one multiply and one ``exp2`` a vreg;
- ``B`` and ``C`` reach the kernel transposed, ``[N, S]`` (the sequence on
  lanes); a 128-step tile of them is loaded once, and the statically
  unrolled steps inside it take their ``[N, 1]`` columns;
- ``y_t`` is a sublane sum. Eight steps' products are summed together by a
  butterfly of sublane rolls, which leaves them as the eight rows of one
  ``[8, 128]`` tile per lane group: a full store, no per-step reduction.

Nothing in the step moves data between lanes and sublanes but the column
broadcasts of ``B_t`` and ``C_t``. The state, ``exp`` and every product stay
f32; ``y`` is written in x's dtype, the final state in f32.

Grid: (batch / batch_fold, D / block_d, S / block_s); the sequence axis is
the sequential one ("arbitrary"), the state carried across it in VMEM
scratch. ``batch_fold`` rows share one program's step as independent tiles
where the state tile is narrow. Inputs are the pre-expansion streams (x, dt,
B, C), so the ``[S, D, N]`` tensors never touch HBM.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
SUBLANES = 8
VREG = SUBLANES * LANES        # f32 elements in one vreg
STATE_VREGS = 8                # state vregs a step carries: fits the 64 vregs
MAX_BLOCK_S = 1024
VMEM_BUDGET = 12 << 20         # of v5e's 16 MiB of scoped VMEM
BITREV8 = (0, 4, 2, 6, 1, 5, 3, 7)


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


@dataclasses.dataclass(frozen=True)
class ScanPlan:
    """How one call is split: blocks, batch fold, padding and grid."""
    seq: int
    d_inner: int
    n_state: int        # states, padded to whole sublane tiles
    block_s: int        # timesteps per grid step
    block_d: int        # channels per program
    batch_fold: int     # batch rows per program
    x_bytes: int        # itemsize of x and y
    dt_bytes: int       # itemsize of dt

    @property
    def s_pad(self) -> int:
        return _round_up(self.seq, self.block_s)

    @property
    def n_chunks(self) -> int:
        return self.s_pad // self.block_s

    @property
    def lane_tile(self) -> int:
        """Steps per B/C column tile: the statically unrolled inner loop."""
        return LANES if self.block_s % LANES == 0 else self.block_s

    @property
    def lane_groups(self) -> int:
        return self.block_d // LANES if self.block_d % LANES == 0 else 1

    def grid(self, batch: int) -> tuple[int, int, int]:
        return (pl.cdiv(batch, self.batch_fold),
                self.d_inner // self.block_d, self.n_chunks)

    def vmem_bytes(self) -> int:
        """VMEM of one program: double-buffered blocks plus scratch."""
        rows, bs, bd, n = self.batch_fold, self.block_s, self.block_d, \
            self.n_state
        io = rows * bs * bd * (2 * self.x_bytes + self.dt_bytes)  # x, dt, y
        bc = 2 * rows * n * _round_up(self.s_pad, LANES) * 4      # B^T, C^T
        small = (n + SUBLANES + rows * n) * bd * 4                # A^T, D, h out
        scratch = rows * (n * bd + 3 * bs * bd) * 4               # h, dt, u, y
        return 2 * (io + bc + small) + scratch


def mamba_scan_plan(batch: int, s: int, d: int, n: int, x_dtype, dt_dtype, *,
                    block_d: int | None = None,
                    block_s: int | None = None) -> ScanPlan:
    """The kernel's tiling for x, dt ``[batch, s, d]`` and B, C
    ``[batch, s, n]``. Explicit blocks are kept (``block_s`` clipped to
    ``s`` and rounded to whole sublane tiles). Otherwise ``block_d`` is the widest multiple of 128 dividing
    ``d`` whose state tile stays within ``STATE_VREGS`` (``d`` itself when
    it is no multiple of 128); batch rows are folded into a program until
    their state tiles fill ``STATE_VREGS``; ``block_s`` is the longest
    multiple of 128 (or the whole, sublane-rounded sequence, when shorter)
    up to ``MAX_BLOCK_S`` whose program fits ``VMEM_BUDGET``."""
    n_state = _round_up(n, SUBLANES)
    if block_d is None:
        if d % LANES:
            block_d = d
        else:
            block_d = LANES
            for cand in range(LANES, d + 1, LANES):
                if d % cand == 0 and n_state * cand <= STATE_VREGS * VREG:
                    block_d = cand
    if d % block_d:
        raise ValueError(f"D={d} must divide into block_d={block_d}")
    state_vregs = -(-n_state // SUBLANES) * -(-block_d // LANES)
    fold = max(1, min(batch, STATE_VREGS // state_vregs))
    plan = functools.partial(
        ScanPlan, seq=s, d_inner=d, n_state=n_state, block_d=block_d,
        batch_fold=fold, x_bytes=jnp.dtype(x_dtype).itemsize,
        dt_bytes=jnp.dtype(dt_dtype).itemsize)
    if block_s is not None:
        return plan(block_s=_round_up(min(block_s, s), SUBLANES))
    if s < LANES:
        return plan(block_s=_round_up(s, SUBLANES))
    block_s = min(_round_up(s, LANES), MAX_BLOCK_S)
    while block_s > LANES and plan(block_s=block_s).vmem_bytes() > VMEM_BUDGET:
        block_s = _round_up(block_s // 2, LANES)
    return plan(block_s=block_s)


def _rows_to_tile(qs):
    """``qs[k]``: ``[8, L]`` partial sums of step k. Returns ``[8, L]``
    whose row k is the whole sum of ``qs[k]``: three butterfly levels of
    sublane rolls (by 4, 2, 1), each halving the tiles; taking the steps in
    bit-reversed order leaves step k in row k."""
    rows = jax.lax.broadcasted_iota(jnp.int32, qs[0].shape, 0)
    q = [qs[k] for k in BITREV8]
    lo = rows < 4                   # a roll by 4 swaps the two halves
    q = [jnp.where(lo, a, b) + pltpu.roll(jnp.where(lo, b, a), 4, 0)
         for a, b in zip(q[0::2], q[1::2])]
    for shift in (2, 1):            # pair rows within each block of 2*shift
        lo = (rows % (2 * shift)) < shift
        q = [jnp.where(lo, a + pltpu.roll(a, SUBLANES - shift, 0),
                       b + pltpu.roll(b, shift, 0))
             for a, b in zip(q[0::2], q[1::2])]
    return q[0]


def _scan_kernel(x_ref, dt_ref, bt_ref, ct_ref, at_ref, d_ref, y_ref,
                 hout_ref, h_ref, dt_s, u_s, y_s, *, plan: ScanPlan,
                 interpret: bool):
    sj = pl.program_id(2)
    rows, n, bd = plan.batch_fold, plan.n_state, plan.block_d
    lt, ng = plan.lane_tile, plan.lane_groups
    lw = bd // ng
    groups = [slice(j * lw, (j + 1) * lw) for j in range(ng)]

    @pl.when(sj == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    # f32 copies of the chunk, one lane group per page, so that a step reads
    # its row of each with one stride-0 load
    for r in range(rows):
        for j, cols in enumerate(groups):
            dt = dt_ref[r, :, cols].astype(jnp.float32)
            dt_s[r, j] = dt
            u_s[r, j] = dt * x_ref[r, :, cols].astype(jnp.float32)
    # exp(dt A) = exp2(dt A log2(e)): the chip's exp is exp2 of its argument
    # times log2(e), so the constant is folded into A once per chunk
    a2 = at_ref[...] * math.log2(math.e)                 # [n, bd]

    def row(ref, r, t):
        """Row t of a paged chunk copy on every sublane: ``[n, bd]``."""
        if interpret or lw != LANES:
            parts = [jnp.broadcast_to(ref[r, j, pl.ds(t, 1), :],
                                      (SUBLANES, lw)) for j in range(ng)]
        else:
            parts = [ref[r, j, pl.ds(t, SUBLANES, stride=0), :]
                     for j in range(ng)]
        v = jnp.concatenate(parts, axis=1) if ng > 1 else parts[0]
        return jnp.concatenate([v] * (n // SUBLANES), axis=0) \
            if n > SUBLANES else v

    def eight(t0, hs, col):
        """Steps t0 .. t0+7 of the chunk; ``col(m, r, k)`` is B (m = 0) or
        C (m = 1) of row r at step t0+k, ``[n, 1]``."""
        hs, qs = list(hs), [[] for _ in range(rows)]
        for k in range(SUBLANES):
            for r in range(rows):
                da = jnp.exp2(row(dt_s, r, t0 + k) * a2)
                hs[r] = da * hs[r] + row(u_s, r, t0 + k) * col(0, r, k)
                p = hs[r] * col(1, r, k)
                q = p[:SUBLANES]
                for i in range(SUBLANES, n, SUBLANES):
                    q = q + p[i:i + SUBLANES]
                qs[r].append(q)
        rows8 = pl.ds(pl.multiple_of(t0, SUBLANES), SUBLANES)
        for r in range(rows):
            y8 = _rows_to_tile(qs[r])                         # [8, bd]
            for j, cols in enumerate(groups):
                y_s[r, j, rows8, :] = y8[:, cols]
        return tuple(hs)

    h0 = tuple(h_ref[r] for r in range(rows))
    if interpret:
        # the same steps as a rolled loop, columns read from the refs: the
        # unrolled form pays only on the chip and costs the interpreter
        # minutes of compiling
        def octet(g, hs):
            t0 = g * SUBLANES
            return eight(t0, hs, lambda m, r, k: (bt_ref, ct_ref)[m][
                r, :, pl.ds(sj * plan.block_s + t0 + k, 1)])
        hs = jax.lax.fori_loop(0, plan.block_s // SUBLANES, octet, h0)
    else:
        def lane_tile(base, hs):    # base: the tile's first step
            col = base if plan.n_chunks == 1 else pl.multiple_of(
                sj * plan.block_s + base, lt)
            tiles = [[ref[r, :, pl.ds(col, lt)] for r in range(rows)]
                     for ref in (bt_ref, ct_ref)]              # [n, lt] each
            for g in range(0, lt, SUBLANES):
                hs = eight(base + g, hs, lambda m, r, k: tiles[m][r][
                    :, g + k:g + k + 1])
            return hs
        if plan.block_s == lt:      # a static column offset below 128 lanes
            hs = lane_tile(0, h0)
        else:
            hs = jax.lax.fori_loop(
                0, plan.block_s // lt,
                lambda i, hs: lane_tile(pl.multiple_of(i * lt, lt), hs), h0)
    for r in range(rows):
        h_ref[r] = hs[r]
        for j, cols in enumerate(groups):
            y_ref[r, :, cols] = (
                y_s[r, j] + x_ref[r, :, cols].astype(jnp.float32)
                * d_ref[:, cols]).astype(y_ref.dtype)

    @pl.when(sj == plan.n_chunks - 1)
    def _emit_state():
        hout_ref[...] = h_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_d", "block_s", "interpret"))
def mamba_scan(x, dt, b_mat, c_mat, a, d_vec, *, block_d: int | None = None,
               block_s: int | None = None, interpret: bool):
    """x, dt: [B,S,D]; b_mat, c_mat: [B,S,N]; a: [D,N]; d_vec: [D].
    Returns (y [B,S,D], h_final [B,D,N]). ``block_d``/``block_s`` override
    the plan's blocks (None: the plan's)."""
    bsz, s, d = x.shape
    n = b_mat.shape[-1]
    plan = mamba_scan_plan(bsz, s, d, n, x.dtype, dt.dtype, block_d=block_d,
                           block_s=block_s)
    bs, bd, rows, nst = plan.block_s, plan.block_d, plan.batch_fold, \
        plan.n_state
    if plan.s_pad > s:
        # zero dt => exp(0*A)=1, dbx=0: padded steps keep the state unchanged
        pad = ((0, 0), (0, plan.s_pad - s), (0, 0))
        x, dt, b_mat, c_mat = (jnp.pad(v, pad) for v in (x, dt, b_mat, c_mat))
    # B, C with the sequence on lanes; padded states have B = C = A = 0
    bc_pad = ((0, 0), (0, nst - n), (0, 0))
    bt = jnp.pad(jnp.swapaxes(b_mat.astype(jnp.float32), 1, 2), bc_pad)
    ct = jnp.pad(jnp.swapaxes(c_mat.astype(jnp.float32), 1, 2), bc_pad)
    at = jnp.pad(a.astype(jnp.float32).T, ((0, nst - n), (0, 0)))

    seq_block = pl.BlockSpec((rows, bs, bd), lambda bi, di, sj: (bi, sj, di))
    bc_block = pl.BlockSpec((rows, nst, plan.s_pad),
                            lambda bi, di, sj: (bi, 0, 0))
    paged = (rows, plan.lane_groups, bs, bd // plan.lane_groups)
    y, h_final = pl.pallas_call(
        functools.partial(_scan_kernel, plan=plan, interpret=interpret),
        grid=plan.grid(bsz),
        in_specs=[
            seq_block, seq_block, bc_block, bc_block,
            pl.BlockSpec((nst, bd), lambda bi, di, sj: (0, di)),
            pl.BlockSpec((1, bd), lambda bi, di, sj: (0, di)),
        ],
        out_specs=[
            seq_block,
            pl.BlockSpec((rows, nst, bd), lambda bi, di, sj: (bi, 0, di)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, plan.s_pad, d), x.dtype),
            jax.ShapeDtypeStruct((bsz, nst, d), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((rows, nst, bd), jnp.float32),  # h
                        pltpu.VMEM(paged, jnp.float32),            # dt
                        pltpu.VMEM(paged, jnp.float32),            # dt * x
                        pltpu.VMEM(paged, jnp.float32)],           # C . h
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="mamba_scan",  # the op's name in HLO and device traces
        interpret=interpret,
    )(x, dt, bt, ct, at, d_vec.reshape(1, d))
    return y[:, :s], jnp.swapaxes(h_final[:, :n], 1, 2)
