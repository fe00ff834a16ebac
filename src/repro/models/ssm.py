"""Mamba-1 selective-state-space block (falcon-mamba, jamba mamba layers).

Jamba's mixer (``cfg.ssm_inner_norms``) adds an RMSNorm on each of dt, B and
C after ``x_proj``, with the model's ``norm_eps``; the Pallas and the
chunked-XLA scans both take their inputs after it.

Prefill/train uses a chunked scan: ``lax.scan`` over sequence chunks with an
associative prefix-scan inside each chunk — O(S) memory in chunk-sized tiles
(mirrors the Pallas ``mamba_scan`` kernel's HBM->VMEM tiling). Decode is the
O(1) recurrence on a carried (conv_state, ssm_state).
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from repro.models.layers import cast_param, dense_init, rmsnorm
from repro.sharding.logical import logical_constraint


def init_mamba(key, cfg, dtype):
    d, di = cfg.d_model, cfg.d_inner
    st, rk, w = cfg.ssm_state_dim, cfg.dt_rank, cfg.ssm_conv_width
    keys = jax.random.split(key, 6)
    # S4D-real initialisation for A; dt bias so softplus(dt) spans [1e-3, 1e-1]
    a = np.tile(np.arange(1, st + 1, dtype=np.float32), (di, 1))
    dt = np.exp(np.random.RandomState(0).uniform(math.log(1e-3), math.log(1e-1), di)
                ).astype(np.float32)
    dt_bias = dt + np.log(-np.expm1(-dt))  # inverse softplus
    norms = {}
    if cfg.ssm_inner_norms:
        norms = {k: jnp.ones((n,), dtype)
                 for k, n in zip(INNER_NORMS, (rk, st, st))}
    return {
        "in_proj": dense_init(keys[0], (d, 2 * di), dtype),
        "conv_w": dense_init(keys[1], (w, di), dtype, fan_in=w),
        "conv_b": jnp.zeros((di,), dtype),
        "x_proj": dense_init(keys[2], (di, rk + 2 * st), dtype, fan_in=di),
        "dt_proj": dense_init(keys[3], (rk, di), dtype, fan_in=rk),
        "dt_bias": jnp.asarray(dt_bias, dtype),
        "A_log": jnp.asarray(np.log(a), dtype=jnp.float32),
        "D": jnp.ones((di,), jnp.float32),
        "out_proj": dense_init(keys[4], (di, d), dtype, fan_in=di),
        **norms,
    }


MAMBA_AXES = {
    "in_proj": ("embed", "ssm_inner"),
    "conv_w": ("conv", "ssm_inner"),
    "conv_b": ("ssm_inner",),
    "x_proj": ("ssm_inner", None),
    "dt_proj": (None, "ssm_inner"),
    "dt_bias": ("ssm_inner",),
    "A_log": ("ssm_inner", "ssm_state"),
    "D": ("ssm_inner",),
    "out_proj": ("ssm_inner", "embed"),
}
INNER_NORMS = ("dt_norm", "b_norm", "c_norm")   # scales, with ssm_inner_norms


def mamba_axes(cfg):
    """Logical axes of ``init_mamba(..., cfg, ...)``'s parameters."""
    axes = dict(MAMBA_AXES)
    if cfg.ssm_inner_norms:
        axes.update({k: (None,) for k in INNER_NORMS})
    return axes


def _causal_conv(x, conv_w, conv_b, history=None):
    """Depthwise causal conv. x: [B,S,di], conv_w: [W,di].
    ``history``: [B,W-1,di] previous inputs (decode) or None (zero-pad)."""
    w = conv_w.shape[0]
    if history is None:
        xp = jnp.pad(x, ((0, 0), (w - 1, 0), (0, 0)))
    else:
        xp = jnp.concatenate([history.astype(x.dtype), x], axis=1)
    out = sum(xp[:, i:i + x.shape[1], :] * conv_w[i] for i in range(w))
    return out + conv_b


def _ssm_inputs(params, x_c, cfg, compute_dtype):
    """Project to (dt [.., di], B [.., st], C [.., st]) — pre state-expansion."""
    rk, st = cfg.dt_rank, cfg.ssm_state_dim
    proj = x_c @ params["x_proj"].astype(compute_dtype)
    dt_r, b_c, c_c = jnp.split(proj, [rk, rk + st], axis=-1)
    if cfg.ssm_inner_norms:
        dt_r = rmsnorm(dt_r, params["dt_norm"], cfg.norm_eps)
        b_c = rmsnorm(b_c, params["b_norm"], cfg.norm_eps)
        c_c = rmsnorm(c_c, params["c_norm"], cfg.norm_eps)
    dt = jax.nn.softplus(
        (dt_r @ params["dt_proj"].astype(compute_dtype)).astype(jnp.float32)
        + params["dt_bias"].astype(jnp.float32))
    return dt, b_c.astype(jnp.float32), c_c.astype(jnp.float32)


def chunked_scan(x, dt, b_mat, c_mat, a, d_vec, *, chunk, h0=None):
    """The selective scan in XLA: ``lax.scan`` over chunks of ``chunk``
    steps, an associative prefix scan inside each. The [chunk, di, st] state
    expansion happens INSIDE the body, so the [S, di, st] tensor never
    materialises in HBM (mirrors the Pallas kernel's per-chunk VMEM
    expansion). x, dt: [B,S,di]; b_mat, c_mat: [B,S,st]; a: [di,st].
    Returns (y [B,S,di] float32, h_final [B,di,st])."""
    b, s, di = x.shape
    st = a.shape[1]
    chunk = min(chunk, s)
    n_chunks = (s + chunk - 1) // chunk
    pad = n_chunks * chunk - s
    xq, dtq, bq, cq = x.astype(jnp.float32), dt, b_mat, c_mat
    if pad:
        # zero dt => exp(0*A)=1, dbx=0: padded steps are identities
        xq, dtq, bq, cq = (jnp.pad(t, ((0, 0), (0, pad), (0, 0)))
                           for t in (xq, dtq, bq, cq))

    def to_chunks(t):
        return t.reshape(b, n_chunks, chunk, t.shape[-1]).swapaxes(0, 1)

    h0 = jnp.zeros((b, di, st), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)

    def chunk_body(h, inp):
        x_ch, dt_ch, b_ch, c_ch = inp            # [b, chunk, ...]
        da_c = jnp.exp(dt_ch[..., None] * a)     # [b, chunk, di, st]
        dbx_c = (dt_ch * x_ch)[..., None] * b_ch[..., None, :]
        a_cum, h_free = jax.lax.associative_scan(
            _ssm_combine, (da_c, dbx_c), axis=1)
        h_all = h_free + a_cum * h[:, None]      # [b, chunk, di, st]
        y_ch = jnp.einsum("bsdn,bsn->bsd", h_all, c_ch)
        return h_all[:, -1], y_ch

    h_final, y_chunks = jax.lax.scan(
        chunk_body, h0, (to_chunks(xq), to_chunks(dtq),
                         to_chunks(bq), to_chunks(cq)))
    y = y_chunks.swapaxes(0, 1).reshape(b, n_chunks * chunk, di)[:, :s]
    return y + d_vec * x.astype(jnp.float32), h_final


def mamba_forward(params, x, cfg, compute_dtype=jnp.bfloat16, state=None):
    """Full-sequence forward. x: [B,S,d] -> (y [B,S,d], final_state)."""
    s = x.shape[1]
    xz = x @ cast_param(params["in_proj"], compute_dtype, *MAMBA_AXES["in_proj"])
    x_in, z = jnp.split(xz, 2, axis=-1)
    x_in = logical_constraint(x_in, "batch", "seq_attn", "ssm_inner")
    conv_hist = None if state is None else state["conv"]
    # the conv and its SiLU in float32, rounded once (as causal_conv1d does)
    f32 = jnp.float32
    x_c = jax.nn.silu(_causal_conv(
        x_in.astype(f32), params["conv_w"].astype(f32),
        params["conv_b"].astype(f32), conv_hist)).astype(compute_dtype)

    dt, b_c, c_c = _ssm_inputs(params, x_c, cfg, compute_dtype)
    a = -jnp.exp(params["A_log"])                      # [di, st]

    if cfg.attn_impl == "pallas" and s > 1 and state is None:
        from repro.kernels import mamba_scan_op
        y, h_final = mamba_scan_op(x_c, dt, b_c, c_c, a, params["D"])
    else:
        h0 = None if state is None else state["ssm"]
        y, h_final = chunked_scan(x_c, dt, b_c, c_c, a, params["D"],
                                  chunk=cfg.ssm_chunk, h0=h0)
    # the gate in float32, rounded once (as the published scan's z gate)
    y = (y.astype(f32) * jax.nn.silu(z.astype(f32))).astype(compute_dtype)
    out = y @ cast_param(params["out_proj"], compute_dtype,
                         *MAMBA_AXES["out_proj"])
    out = logical_constraint(out, "batch", "seq_q", "embed_act")

    new_state = {
        "conv": _conv_tail(x_in, cfg.ssm_conv_width, conv_hist),
        "ssm": h_final,
    }
    return out, new_state


def _ssm_combine(e1, e2):
    a1, b1 = e1
    a2, b2 = e2
    return a2 * a1, a2 * b1 + b2


def _conv_tail(x_in, width, history):
    """Last W-1 inputs, for decode continuation."""
    b, s, di = x_in.shape
    need = width - 1
    if history is not None:
        x_in = jnp.concatenate([history.astype(x_in.dtype), x_in], axis=1)
        s = x_in.shape[1]
    if s >= need:
        return x_in[:, s - need:s]
    pad = need - s
    return jnp.pad(x_in, ((0, 0), (pad, 0), (0, 0)))


def mamba_decode_step(params, x, state, cfg, compute_dtype=jnp.bfloat16):
    """Single-token recurrence. x: [B,1,d]; state {conv [B,W-1,di], ssm [B,di,st]}."""
    out, new_state = mamba_forward(params, x, cfg, compute_dtype, state=state)
    return out, new_state


def init_mamba_state(batch, cfg, dtype=jnp.bfloat16):
    return {
        "conv": jnp.zeros((batch, cfg.ssm_conv_width - 1, cfg.d_inner), dtype),
        "ssm": jnp.zeros((batch, cfg.d_inner, cfg.ssm_state_dim), jnp.float32),
    }


MAMBA_STATE_AXES = {
    "conv": ("batch", None, "ssm_inner"),
    "ssm": ("batch", "ssm_inner", "ssm_state"),
}
