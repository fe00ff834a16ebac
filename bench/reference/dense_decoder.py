"""Plain float32 reference of a dense decoder-only language model.

It imports nothing of the program under test. The weights are made here,
from the run's seed: the benchmark builds the served program's parameters
from ``make_layer`` and ``make_top`` as well, so the reference computes on the
same numbers without taking any array that the program made.

The layer follows the published description of the configurations that name
this family: pre-norm (LayerNorm with bias, or RMSNorm), rotary position
embedding on the first ``rotary_dim`` dims of each head (rotate-half
pairing), grouped-query causal attention (optionally windowed), a GELU (tanh
form) or SwiGLU MLP, and a tied output head read at the last position. All
arithmetic is float32 with matmuls at ``highest`` precision; the weights are
the bfloat16 numbers that the program serves, upcast.

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

HIGHEST = jax.lax.Precision.HIGHEST
_U32 = 0xFFFFFFFF
_ACT = {"gelu_pytorch_tanh": "gelu", "gelu": "gelu", "silu": "swiglu"}
_NORM = {"layer_norm": "layernorm", "rms_norm": "rmsnorm"}


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
    mlp: str          # "gelu" | "swiglu"
    norm: str         # "layernorm" | "rmsnorm"
    eps: float
    rope_theta: float
    rotary_dim: int
    window: int       # 0: full causal attention


def dims(cfg: dict) -> Dims:
    """Read a configuration file's published keys."""
    if not cfg.get("tie_word_embeddings"):
        raise ValueError(f"{cfg['name']}: this reference ties the output "
                         "head to the embedding")
    heads = cfg["num_attention_heads"]
    head_dim = cfg.get("head_dim") or cfg["hidden_size"] // heads
    eps = cfg.get("layer_norm_epsilon", cfg.get("rms_norm_eps"))
    return Dims(
        d=cfg["hidden_size"], layers=cfg["num_hidden_layers"], heads=heads,
        kv_heads=cfg["num_key_value_heads"], head_dim=head_dim,
        ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
        mlp=_ACT[cfg["hidden_act"]], norm=_NORM[cfg["norm_type"]],
        eps=float(eps), rope_theta=float(cfg["rope_theta"]),
        rotary_dim=int(round(head_dim
                             * cfg.get("partial_rotary_factor", 1.0))),
        window=int(cfg.get("sliding_window") or 0))


# --------------------------------------------------------------------------- #
# seeded weights
# --------------------------------------------------------------------------- #

def layer_leaves(dm: Dims):
    """(name, shape, init) of one layer's weights. ``init`` is the fan-in of
    a matrix, or "scale" / "bias" for a norm."""
    q, kv = dm.heads * dm.head_dim, dm.kv_heads * dm.head_dim
    out = [("attn_norm_scale", (dm.d,), "scale")]
    if dm.norm == "layernorm":
        out.append(("attn_norm_bias", (dm.d,), "bias"))
    out += [("wq", (dm.d, q), dm.d), ("wk", (dm.d, kv), dm.d),
            ("wv", (dm.d, kv), dm.d), ("wo", (q, dm.d), q),
            ("mlp_norm_scale", (dm.d,), "scale")]
    if dm.norm == "layernorm":
        out.append(("mlp_norm_bias", (dm.d,), "bias"))
    if dm.mlp == "swiglu":      # [:, 0] gate (through SiLU), [:, 1] up
        out.append(("w_in", (dm.d, 2, dm.ff), dm.d))
    else:
        out.append(("w_up", (dm.d, dm.ff), dm.d))
    out.append(("w_down", (dm.ff, dm.d), dm.ff))
    return out


def top_leaves(dm: Dims):
    out = [("embed", (dm.vocab, dm.d), dm.d),
           ("final_norm_scale", (dm.d,), "scale")]
    if dm.norm == "layernorm":
        out.append(("final_norm_bias", (dm.d,), "bias"))
    return out


def _leaf(key, shape, init, dtype):
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


def expert_key(seed: int, expert: int):
    """The key of one expert of a run: every whole-number seed, also one
    beyond 32 bits, gives its own key."""
    k = jax.random.PRNGKey(np.uint32(seed & _U32))
    k = jax.random.fold_in(k, np.uint32((seed >> 32) & _U32))
    return jax.random.fold_in(k, np.uint32(expert))


def layer_key(ek, layer):
    return jax.random.fold_in(jax.random.fold_in(ek, 0), layer)


def top_key(ek):
    return jax.random.fold_in(ek, 1)


def make_layer(key, dm: Dims, dtype=jnp.bfloat16):
    return _make(key, layer_leaves(dm), dtype)


def make_top(key, dm: Dims, dtype=jnp.bfloat16):
    return _make(key, top_leaves(dm), dtype)


def _fp8(x, axis):
    """x rounded to float8 e4m3, one scale per slice along ``axis`` (the
    contraction axis): the slice's largest magnitude maps to float8's
    largest, 448."""
    scale = jnp.max(jnp.abs(x), axis=axis, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _upcast(ws: dict):
    return {k: v.astype(jnp.float32) for k, v in ws.items()}


# --------------------------------------------------------------------------- #
# forward
# --------------------------------------------------------------------------- #

class _Ops:
    """The forward's matrix products: float32 (the reference), or with every
    operand rounded to float8 first and float32 accumulation (the control:
    the reference computed in the precision below the configuration's
    bfloat16)."""

    def __init__(self, fp8: bool):
        self.fp8 = fp8

    def mm(self, a, b):
        if self.fp8:
            a, b = _fp8(a, -1), _fp8(b, 0)
        return jnp.matmul(a, b, precision=HIGHEST)

    def einsum(self, spec, a, b, axes):
        if self.fp8:
            a, b = _fp8(a, axes[0]), _fp8(b, axes[1])
        return jnp.einsum(spec, a, b, precision=HIGHEST)


def _norm(x, w, prefix, dm: Dims):
    if dm.norm == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return ((x - mu) / jnp.sqrt(var + dm.eps) * w[prefix + "_scale"]
                + w[prefix + "_bias"])
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x / jnp.sqrt(ms + dm.eps) * w[prefix + "_scale"]


def _rope(x, dm: Dims):
    """x: [B, S, H, hd]; positions 0..S-1."""
    r, half = dm.rotary_dim, dm.rotary_dim // 2
    inv = 1.0 / dm.rope_theta ** (np.arange(0, r, 2, dtype=np.float64) / r)
    ang = np.arange(x.shape[1], dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2, rest = x[..., :half], x[..., half:r], x[..., r:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest],
                           axis=-1)


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def layer_forward(w: dict, x, dm: Dims, ops: _Ops = _Ops(False)):
    """One pre-norm layer on x: [B, S, d] float32."""
    b, s, _ = x.shape
    hd = dm.head_dim
    h = _norm(x, w, "attn_norm", dm)
    q = _rope(ops.mm(h, w["wq"]).reshape(b, s, dm.heads, hd), dm)
    k = _rope(ops.mm(h, w["wk"]).reshape(b, s, dm.kv_heads, hd), dm)
    v = ops.mm(h, w["wv"]).reshape(b, s, dm.kv_heads, hd)
    g = dm.heads // dm.kv_heads            # query head i reads kv head i // g
    k, v = jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)
    scores = ops.einsum("bqhd,bkhd->bhqk", q, k, (-1, -1)) / math.sqrt(hd)
    qi, ki = np.arange(s)[:, None], np.arange(s)[None, :]
    allowed = ki <= qi
    if dm.window:
        allowed &= qi - ki < dm.window
    scores = jnp.where(allowed[None, None], scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    att = ops.einsum("bhqk,bkhd->bqhd", p, v, (-1, 1))
    x = x + ops.mm(att.reshape(b, s, dm.heads * hd), w["wo"])
    h = _norm(x, w, "mlp_norm", dm)
    if dm.mlp == "swiglu":
        gate = ops.mm(h, w["w_in"][:, 0, :])
        up = ops.mm(h, w["w_in"][:, 1, :])
        h = gate * jax.nn.sigmoid(gate) * up
    else:
        h = _gelu_tanh(ops.mm(h, w["w_up"]))
    return x + ops.mm(h, w["w_down"])


@functools.lru_cache(maxsize=8)
def _programs(dm: Dims, block: int, seq: int, precision: str):
    if precision not in ("float32", "float8_e4m3fn"):
        raise ValueError(f"unknown precision {precision!r}")
    ops = _Ops(precision == "float8_e4m3fn")

    @jax.jit
    def top(key):
        return _upcast(make_top(key, dm))

    @jax.jit
    def layer_weights(key):
        return _upcast(make_layer(key, dm))

    @jax.jit
    def embed(t, tokens):
        return t["embed"][tokens]

    @jax.jit
    def layer(w, x):
        return layer_forward(w, x, dm, ops)

    @jax.jit
    def head(t, x):
        last = _norm(x[:, -1], t, "final_norm", dm)
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
        w = layer_weights(layer_key(ek, li))
        xs = [layer(w, x) for x in xs]
        del w
    out = np.concatenate([np.asarray(head(t, x)) for x in xs])
    return out[:n]
