"""The served program's side of a dense decoder cell.

``program_config`` turns a configuration file into the program's
``ModelConfig`` as the benchmark serves it (bfloat16 weights, the Pallas
attention kernel, no rematerialisation). ``init_fn`` builds one expert's
parameters on the device, in one jitted call, in the program's own layout,
from the same seeded numbers as the reference. ``serve_fn`` is the model
step the window drives: the jitted forward, read at the last position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import dense_decoder as ref
from repro.models import transformer
from repro.models.config import ModelConfig


def program_config(cfg: dict) -> ModelConfig:
    dm = ref.dims(cfg)
    if dm.rotary_dim != dm.head_dim:
        raise ValueError(f"{cfg['name']}: the program rotates whole heads; "
                         "set partial_rotary_factor to 1.0")
    return ModelConfig(
        name=cfg["name"], family="dense", num_layers=dm.layers,
        d_model=dm.d, num_heads=dm.heads, num_kv_heads=dm.kv_heads,
        head_dim=dm.head_dim, d_ff=dm.ff, vocab_size=dm.vocab,
        rope_theta=dm.rope_theta, sliding_window=dm.window,
        mlp_type=dm.mlp, norm_type=dm.norm, norm_eps=dm.eps,
        tie_embeddings=True, param_dtype="bfloat16",
        compute_dtype="bfloat16", attn_impl="pallas", remat=False)


def _program_tree(layers: dict, top: dict, dm: ref.Dims) -> dict:
    def norm(prefix, src):
        out = {"scale": src[prefix + "_scale"]}
        if dm.norm == "layernorm":
            out["bias"] = src[prefix + "_bias"]
        return out

    mlp = ({"w_in": layers["w_in"]} if dm.mlp == "swiglu"
           else {"w_up": layers["w_up"]})
    mlp["w_down"] = layers["w_down"]
    return {
        "embed": {"table": top["embed"]},
        "slots": {"slot0": {
            "norm1": norm("attn_norm", layers),
            "attn": {k: layers[k] for k in ("wq", "wk", "wv", "wo")},
            "norm2": norm("mlp_norm", layers),
            "mlp": mlp}},
        "final_norm": norm("final_norm", top),
    }


def init_fn(cfg: dict):
    """Jitted ``fn(expert_key) -> params``: one expert's weights, made on
    the device layer by layer (``lax.map`` writes each layer into the
    stacked arrays, so no second copy is held)."""
    dm = ref.dims(cfg)

    def build(ek):
        layers = jax.lax.map(
            lambda li: ref.make_layer(ref.layer_key(ek, li), dm),
            jnp.arange(dm.layers))
        return _program_tree(layers, ref.make_top(ref.top_key(ek), dm), dm)

    want = transformer.abstract_params(program_config(cfg))
    got = jax.eval_shape(build, ref.expert_key(0, 0))
    if jax.tree.structure(want) != jax.tree.structure(got) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(want), jax.tree.leaves(got))):
        raise ValueError(f"{cfg['name']}: the seeded weights do not match "
                         "the program's parameter layout")
    return jax.jit(build)


def serve_fn(cfg: dict):
    """Jitted ``fn(params, tokens [B, S]) -> float32 logits [B, vocab]`` at
    the last position."""
    mc = program_config(cfg)

    @jax.jit
    def fn(params, tokens):
        logits, _ = transformer.forward(params, tokens, mc, mode="eval")
        return logits[:, -1].astype(jnp.float32)
    return fn
