"""Chunked Mamba-1 selective scan — Pallas TPU kernel.

One program owns a [block_d] slice of the inner channels for one batch row;
the sequence axis is the sequential grid dimension in [block_s] chunks, with
the SSM state h [block_d, N] carried in VMEM scratch across chunks. Within a
chunk the recurrence h_t = exp(dt_t A) h_{t-1} + dt_t B_t x_t runs as a
``fori_loop`` over timesteps on VMEM-resident tiles (N = 16 keeps the state
tile narrow; block_d is 128-aligned for the VPU lanes).

Inputs are the *pre-projection* streams (x, dt, B, C) so the [S, D, N]
expanded tensors never touch HBM — the kernel materialises them only per
chunk in VMEM, which is the core memory saving of the Mamba scan on TPU.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _scan_kernel(x_ref, dt_ref, b_ref, c_ref, a_ref, d_ref, y_ref, hout_ref,
                 h_ref, x_s, dt_s, b_s, c_s, y_s, *, block_s, seq_len,
                 n_chunks):
    sj = pl.program_id(2)

    @pl.when(sj == 0)
    def _init():
        h_ref[...] = jnp.zeros_like(h_ref)

    # f32 copies of the chunk; each timestep reads its row from these refs
    # with ``pl.ds(t, 1)`` (Mosaic lowers no dynamic_slice of a loaded
    # value, and single-row slices are tile-aligned only for 32-bit data)
    x_s[...] = x_ref[0].astype(jnp.float32)      # [bs, bd]
    dt_s[...] = dt_ref[0].astype(jnp.float32)    # [bs, bd]
    b_s[...] = b_ref[0].astype(jnp.float32)      # [bs, N]
    c_s[...] = c_ref[0].astype(jnp.float32)      # [bs, N]
    a = a_ref[...].astype(jnp.float32)           # [bd, N]

    def step(t, h):
        row = pl.ds(t, 1)
        dt_t = dt_s[row, :]                                       # [1, bd]
        da = jnp.exp(dt_t.reshape(-1, 1) * a)                     # [bd, N]
        dbx = (dt_t * x_s[row, :]).reshape(-1, 1) * b_s[row, :]   # [bd, N]
        h = da * h + dbx
        y_s[row, :] = jnp.sum(h * c_s[row, :], axis=1).reshape(1, -1)
        return h

    h_ref[...] = jax.lax.fori_loop(0, block_s, step, h_ref[...])
    d_vec = d_ref[...].astype(jnp.float32)       # [1, bd]
    y_ref[0] = (y_s[...] + x_s[...] * d_vec).astype(y_ref.dtype)

    @pl.when(sj == n_chunks - 1)
    def _emit_state():
        hout_ref[0] = h_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_d", "block_s", "interpret"))
def mamba_scan(x, dt, b_mat, c_mat, a, d_vec, *, block_d: int = 128,
               block_s: int = 128, interpret: bool):
    """x, dt: [B,S,D]; b_mat, c_mat: [B,S,N]; a: [D,N]; d_vec: [D].
    Returns (y [B,S,D], h_final [B,D,N])."""
    bsz, s, d = x.shape
    n = b_mat.shape[-1]
    block_d = min(block_d, d)
    block_s = min(block_s, s)
    nd = pl.cdiv(d, block_d)
    ns = pl.cdiv(s, block_s)
    if nd * block_d != d:
        raise ValueError(f"D={d} must divide into block_d={block_d}")
    s_pad = ns * block_s - s
    if s_pad:
        # zero dt => exp(0*A)=1, dbx=0: padded steps keep the state unchanged
        x = jnp.pad(x, ((0, 0), (0, s_pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, s_pad), (0, 0)))
        b_mat = jnp.pad(b_mat, ((0, 0), (0, s_pad), (0, 0)))
        c_mat = jnp.pad(c_mat, ((0, 0), (0, s_pad), (0, 0)))

    kernel = functools.partial(_scan_kernel, block_s=block_s, seq_len=s,
                               n_chunks=ns)
    y, h_final = pl.pallas_call(
        kernel,
        grid=(bsz, nd, ns),
        in_specs=[
            pl.BlockSpec((1, block_s, block_d), lambda bi, di, sj: (bi, sj, di)),
            pl.BlockSpec((1, block_s, block_d), lambda bi, di, sj: (bi, sj, di)),
            pl.BlockSpec((1, block_s, n), lambda bi, di, sj: (bi, sj, 0)),
            pl.BlockSpec((1, block_s, n), lambda bi, di, sj: (bi, sj, 0)),
            pl.BlockSpec((block_d, n), lambda bi, di, sj: (di, 0)),
            pl.BlockSpec((1, block_d), lambda bi, di, sj: (0, di)),
        ],
        out_specs=[
            pl.BlockSpec((1, block_s, block_d), lambda bi, di, sj: (bi, sj, di)),
            pl.BlockSpec((1, block_d, n), lambda bi, di, sj: (bi, di, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bsz, s + s_pad, d), x.dtype),
            jax.ShapeDtypeStruct((bsz, d, n), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((block_d, n), jnp.float32),      # h
                        pltpu.VMEM((block_s, block_d), jnp.float32),  # x
                        pltpu.VMEM((block_s, block_d), jnp.float32),  # dt
                        pltpu.VMEM((block_s, n), jnp.float32),        # B
                        pltpu.VMEM((block_s, n), jnp.float32),        # C
                        pltpu.VMEM((block_s, block_d), jnp.float32)],  # y
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        name="mamba_scan",  # the op's name in HLO and device traces
        interpret=interpret,
    )(x, dt, b_mat, c_mat, a, d_vec.reshape(1, d))
    return y[:, :s], h_final
