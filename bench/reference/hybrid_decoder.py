"""Plain float32 reference of a hybrid Mamba-1 / attention decoder (Jamba).

It imports nothing of the program under test. The weights are made here,
from the run's seed, layer by layer with the keys of ``dense_decoder``
(``expert_key``, ``layer_key``, ``top_key``): the benchmark builds the
served program's parameters from ``make_layer`` and ``make_top`` as well, so
the reference computes on the same numbers without taking any array that
the program made.

The layer follows the published Jamba decoder (``model_type`` "jamba"):
layer ``i`` mixes with attention where ``i % attn_layer_period ==
attn_layer_offset`` and with a Mamba-1 mixer elsewhere; each mixer and the
SwiGLU MLP after it are pre-RMSNorm residual branches, and the output head
is tied to the embedding and read at the last position. Attention is causal
and grouped-query, with no positional encoding. The Mamba-1 mixer:

    x, z   = split(in_proj(h))                    # [S, d_inner] each
    x      = silu(causal depthwise conv(x) + conv bias)
    dt, B, C = split(x_proj(x));  dt, B, C = RMSNorm each
    dt     = softplus(dt_proj(dt) + dt_bias)
    h_t    = exp(dt_t A) h_{t-1} + dt_t x_t B_t;   y_t = C_t . h_t + D x_t
    out    = out_proj(y * silu(z))

with ``A = -exp(A_log)``. The recurrence is a plain ``lax.scan`` over time.
All arithmetic is float32 with matmuls at ``highest`` precision; the weights
are the bfloat16 numbers that the program serves, upcast (``A_log`` and
``D`` are float32 in both).

``last_logits(..., precision="float8_e4m3fn")`` is the control: the same
forward with both operands of every matrix product rounded to float8 (one
scale per slice along the contraction), the precision below the
configuration's bfloat16.
"""
from __future__ import annotations

import dataclasses
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# the same keys as the dense reference; the harness calls ``expert_key`` of
# the configuration's reference module
from bench.reference.dense_decoder import (_Ops, expert_key, layer_key,
                                           top_key)

DT_MIN, DT_MAX, DT_FLOOR = 1e-3, 1e-1, 1e-4     # published dt init range


@dataclasses.dataclass(frozen=True)
class Dims:
    """The sizes of a configuration file, under short names."""
    d: int
    layers: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    eps: float
    attn_period: int
    attn_offset: int
    d_inner: int
    d_state: int
    dt_rank: int
    d_conv: int


def dims(cfg: dict) -> Dims:
    """Read a configuration file's published keys, refusing what this
    reference does not compute."""
    refuse = {
        "tie_word_embeddings": not cfg.get("tie_word_embeddings"),
        "num_experts": cfg.get("num_experts", 1) != 1,
        "hidden_act": cfg["hidden_act"] != "silu",
        "mamba_proj_bias": bool(cfg.get("mamba_proj_bias")),
        "mamba_conv_bias": not cfg.get("mamba_conv_bias", True),
        "sliding_window": bool(cfg.get("sliding_window")),
    }
    bad = [k for k, v in refuse.items() if v]
    if bad:
        raise ValueError(f"{cfg['name']}: this reference computes tied "
                         "embeddings, dense SiLU MLPs, conv bias and no "
                         f"projection bias or window; not {bad}")
    heads = cfg["num_attention_heads"]
    d = cfg["hidden_size"]
    return Dims(
        d=d, layers=cfg["num_hidden_layers"], heads=heads,
        kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg.get("head_dim") or d // heads,
        ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        eps=float(cfg["rms_norm_eps"]),
        attn_period=cfg["attn_layer_period"],
        attn_offset=cfg["attn_layer_offset"],
        d_inner=cfg["mamba_expand"] * d, d_state=cfg["mamba_d_state"],
        dt_rank=cfg["mamba_dt_rank"], d_conv=cfg["mamba_d_conv"])


def layer_kind(dm: Dims, layer: int) -> str:
    return "attn" if layer % dm.attn_period == dm.attn_offset else "mamba"


# --------------------------------------------------------------------------- #
# seeded weights
# --------------------------------------------------------------------------- #

def layer_leaves(dm: Dims, kind: str):
    """(name, shape, init) of one layer's weights. ``init`` is the fan-in of
    a matrix, or "scale" / "bias" / "dt_bias" / "a_log" / "ones"."""
    out = [("norm1_scale", (dm.d,), "scale")]
    if kind == "attn":
        q, kv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
        out += [("wq", (dm.d, q), dm.d), ("wk", (dm.d, kv), dm.d),
                ("wv", (dm.d, kv), dm.d), ("wo", (q, dm.d), q)]
    else:
        di, st, rk = dm.d_inner, dm.d_state, dm.dt_rank
        out += [("in_proj", (dm.d, 2 * di), dm.d),
                ("conv_w", (dm.d_conv, di), dm.d_conv),
                ("conv_b", (di,), "bias"),
                ("x_proj", (di, rk + 2 * st), di),
                ("dt_norm", (rk,), "scale"), ("b_norm", (st,), "scale"),
                ("c_norm", (st,), "scale"),
                ("dt_proj", (rk, di), rk), ("dt_bias", (di,), "dt_bias"),
                ("A_log", (di, st), "a_log"), ("D", (di,), "ones"),
                ("out_proj", (di, dm.d), di)]
    # [:, 0] gate (through SiLU), [:, 1] up
    out += [("norm2_scale", (dm.d,), "scale"),
            ("w_in", (dm.d, 2, dm.ff), dm.d), ("w_down", (dm.ff, dm.d), dm.ff)]
    return out


def top_leaves(dm: Dims):
    return [("embed", (dm.vocab, dm.d), dm.d),
            ("final_norm_scale", (dm.d,), "scale")]


def _leaf(key, shape, init, dtype):
    """One weight. A_log and D are float32, as the published model keeps
    them; everything else is in ``dtype``."""
    if init == "a_log":          # S4D-real: A[:, n] = -(n + 1); a constant,
        # so every program that makes it holds the same bits
        return jnp.asarray(np.broadcast_to(
            np.log(np.arange(1, shape[1] + 1, dtype=np.float32)), shape))
    if init == "ones":
        return jnp.ones(shape, jnp.float32)
    if init == "dt_bias":        # softplus(dt_bias) log-uniform in the range
        u = jax.random.uniform(key, shape, jnp.float32)
        dt = jnp.maximum(jnp.exp(u * (math.log(DT_MAX) - math.log(DT_MIN))
                                 + math.log(DT_MIN)), DT_FLOOR)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    z = jax.random.normal(key, shape, jnp.float32)
    if init == "scale":
        v = 1.0 + 0.1 * z
    elif init == "bias":
        v = 0.1 * z
    else:
        v = z * (1.0 / math.sqrt(init))
    return v.astype(dtype)


def _make(key, leaves, dtype):
    return {name: _leaf(jax.random.fold_in(key, i), shape, init, dtype)
            for i, (name, shape, init) in enumerate(leaves)}


def make_layer(key, dm: Dims, kind: str, dtype=jnp.bfloat16):
    return _make(key, layer_leaves(dm, kind), dtype)


def make_top(key, dm: Dims, dtype=jnp.bfloat16):
    return _make(key, top_leaves(dm), dtype)


def _upcast(ws: dict):
    return {k: v.astype(jnp.float32) for k, v in ws.items()}


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #

def _rms(x, scale, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
        * scale


def _silu(x):
    return x * jax.nn.sigmoid(x)


def _mlp(w, x, dm: Dims, ops: _Ops):
    h = _rms(x, w["norm2_scale"], dm.eps)
    gate = ops.mm(h, w["w_in"][:, 0, :])
    up = ops.mm(h, w["w_in"][:, 1, :])
    return x + ops.mm(_silu(gate) * up, w["w_down"])


def attention_mixer(w, h, dm: Dims, ops: _Ops):
    """Causal grouped-query attention with no positional encoding."""
    b, s, _ = h.shape
    hd = dm.head_dim
    q = ops.mm(h, w["wq"]).reshape(b, s, dm.heads, hd)
    k = ops.mm(h, w["wk"]).reshape(b, s, dm.kv_heads, hd)
    v = ops.mm(h, w["wv"]).reshape(b, s, dm.kv_heads, hd)
    g = dm.heads // dm.kv_heads            # query head i reads kv head i // g
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = ops.einsum("bqhd,bkhd->bhqk", q, k, (-1, -1)) / math.sqrt(hd)
    allowed = np.arange(s)[None, :] <= np.arange(s)[:, None]
    scores = jnp.where(allowed[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    att = ops.einsum("bhqk,bkhd->bqhd", p, v, (-1, 1))
    return ops.mm(att.reshape(b, s, dm.heads * hd), w["wo"])


def selective_scan(x, dt, bm, cm, a, d_vec):
    """y_t = C_t . h_t + D x_t with h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t
    and h_0 = 0, one time step at a time. x, dt: [B, S, di]; bm, cm:
    [B, S, st]; a: [di, st]."""
    a_t = a.T                                          # [st, di]

    def step(h, inp):                                  # h: [B, st, di]
        x_t, dt_t, b_t, c_t = inp
        h = jnp.exp(dt_t[:, None, :] * a_t) * h \
            + (dt_t * x_t)[:, None, :] * b_t[:, :, None]
        return h, jnp.sum(h * c_t[:, :, None], axis=1)

    h0 = jnp.zeros((x.shape[0], a.shape[1], a.shape[0]), jnp.float32)
    _, ys = jax.lax.scan(step, h0, tuple(jnp.swapaxes(t, 0, 1)
                                         for t in (x, dt, bm, cm)))
    return jnp.swapaxes(ys, 0, 1) + d_vec * x


def mamba_mixer(w, h, dm: Dims, ops: _Ops):
    s = h.shape[1]
    rk, st = dm.dt_rank, dm.d_state
    x, z = jnp.split(ops.mm(h, w["in_proj"]), 2, axis=-1)
    xp = jnp.pad(x, ((0, 0), (dm.d_conv - 1, 0), (0, 0)))
    x = _silu(sum(xp[:, i:i + s] * w["conv_w"][i] for i in range(dm.d_conv))
              + w["conv_b"])
    dt, bm, cm = jnp.split(ops.mm(x, w["x_proj"]), [rk, rk + st], axis=-1)
    dt = _rms(dt, w["dt_norm"], dm.eps)
    bm = _rms(bm, w["b_norm"], dm.eps)
    cm = _rms(cm, w["c_norm"], dm.eps)
    dt = jax.nn.softplus(ops.mm(dt, w["dt_proj"]) + w["dt_bias"])
    y = selective_scan(x, dt, bm, cm, -jnp.exp(w["A_log"]), w["D"])
    return ops.mm(y * _silu(z), w["out_proj"])


def layer_forward(w: dict, x, dm: Dims, kind: str, ops: _Ops = _Ops(False)):
    """One layer on x: [B, S, d] float32."""
    h = _rms(x, w["norm1_scale"], dm.eps)
    mixer = attention_mixer if kind == "attn" else mamba_mixer
    return _mlp(w, x + mixer(w, h, dm, ops), dm, ops)


@functools.lru_cache(maxsize=8)
def _programs(dm: Dims, block: int, seq: int, precision: str):
    if precision not in ("float32", "float8_e4m3fn"):
        raise ValueError(f"unknown precision {precision!r}")
    ops = _Ops(precision == "float8_e4m3fn")

    @jax.jit
    def top(key):
        return _upcast(make_top(key, dm))

    @functools.partial(jax.jit, static_argnums=1)
    def layer_weights(key, kind):
        return _upcast(make_layer(key, dm, kind))

    @jax.jit
    def embed(t, tokens):
        return t["embed"][tokens]

    @functools.partial(jax.jit, static_argnums=2)
    def layer(w, x, kind):
        return layer_forward(w, x, dm, kind, ops)

    @jax.jit
    def head(t, x):
        last = _rms(x[:, -1], t["final_norm_scale"], dm.eps)
        return ops.mm(last, t["embed"].T)

    return top, layer_weights, embed, layer, head


def last_logits(cfg: dict, seed: int, expert: int, tokens: np.ndarray, *,
                precision: str = "float32",
                block_tokens: int = 8192) -> np.ndarray:
    """float32 logits [n, vocab] at the last position of each row of
    ``tokens`` ([n, S] ids) under expert ``expert`` of seed ``seed``. It
    runs layer by layer, in blocks of rows of at most ``block_tokens``
    tokens, so that only one layer's weights are on the device at a time."""
    dm = dims(cfg)
    n, seq = tokens.shape
    # one block shape per prompt length, whatever ``n``: the compiled
    # programs are found again in the compile cache by the next run
    block = max(1, block_tokens // seq)
    pad = -n % block
    tokens = np.concatenate([tokens, np.zeros((pad, seq), tokens.dtype)]) \
        if pad else tokens
    top, layer_weights, embed, layer, head = _programs(dm, block, seq,
                                                       precision)
    ek = expert_key(seed, expert)
    t = top(top_key(ek))
    xs = [embed(t, jnp.asarray(tokens[i:i + block], jnp.int32))
          for i in range(0, len(tokens), block)]
    for li in range(dm.layers):
        kind = layer_kind(dm, li)
        w = layer_weights(layer_key(ek, li), kind)
        xs = [layer(w, x, kind) for x in xs]
        del w
    out = np.concatenate([np.asarray(head(t, x)) for x in xs])
    return out[:n]
