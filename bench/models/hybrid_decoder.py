"""The served program's side of a hybrid Mamba-1 / attention cell (Jamba).

``program_config`` turns a configuration file into the program's
``ModelConfig`` as the benchmark serves it (bfloat16 weights, the Pallas
attention and scan kernels, no rematerialisation). ``init_fn`` builds one
expert's parameters on the device, in one jitted call, in the program's own
layout, from the same seeded numbers as the reference. ``serve_fn`` is the
model step the window drives: the jitted forward, read at the last position.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import hybrid_decoder as ref
from repro.models import transformer
from repro.models.config import ModelConfig


def program_config(cfg: dict) -> ModelConfig:
    """The program's layer pattern is one period of ``attn_layer_period``
    layers scanned over the depth; a model shallower than a period is the
    first ``num_hidden_layers`` layers of one."""
    dm = ref.dims(cfg)
    period = min(dm.attn_period, dm.layers)
    if dm.layers % period:
        raise ValueError(f"{cfg['name']}: {dm.layers} layers are not whole "
                         f"periods of {period}")
    return ModelConfig(
        name=cfg["name"], family="hybrid", num_layers=dm.layers,
        d_model=dm.d, num_heads=dm.heads, num_kv_heads=dm.kv_heads,
        head_dim=dm.head_dim, d_ff=dm.ff, vocab_size=dm.vocab,
        attn_period=period, attn_offset=dm.attn_offset,
        ssm_state_dim=dm.d_state, ssm_conv_width=dm.d_conv,
        ssm_expand=dm.d_inner // dm.d, ssm_dt_rank=dm.dt_rank,
        ssm_inner_norms=True, position_encoding="none",
        mlp_type="swiglu", norm_type="rmsnorm", norm_eps=dm.eps,
        tie_embeddings=True, param_dtype="bfloat16",
        compute_dtype="bfloat16", attn_impl="pallas", remat=False)


def _slot(w: dict, kind: str) -> dict:
    if kind == "attn":
        mixer = {"attn": {k: w[k] for k in ("wq", "wk", "wv", "wo")}}
    else:
        mixer = {"mamba": {k: w[k] for k in (
            "in_proj", "conv_w", "conv_b", "x_proj", "dt_norm", "b_norm",
            "c_norm", "dt_proj", "dt_bias", "A_log", "D", "out_proj")}}
    return {"norm1": {"scale": w["norm1_scale"]}, **mixer,
            "norm2": {"scale": w["norm2_scale"]},
            "mlp": {"w_in": w["w_in"], "w_down": w["w_down"]}}


def init_fn(cfg: dict):
    """Jitted ``fn(expert_key) -> params``: one expert's weights, made on
    the device layer by layer. Slot ``i`` of period ``p`` is layer
    ``p * period + i``; ``lax.map`` over the periods writes each layer into
    its slot's stacked arrays, so no second copy is held."""
    dm = ref.dims(cfg)
    mc = program_config(cfg)
    period, periods = mc.period(), mc.num_periods()

    def build(ek):
        slots = {}
        for i in range(period):
            kind = ref.layer_kind(dm, i)
            w = jax.lax.map(
                lambda p, i=i, kind=kind: ref.make_layer(
                    ref.layer_key(ek, p * period + i), dm, kind),
                jnp.arange(periods))
            slots[f"slot{i}"] = _slot(w, kind)
        top = ref.make_top(ref.top_key(ek), dm)
        return {"embed": {"table": top["embed"]}, "slots": slots,
                "final_norm": {"scale": top["final_norm_scale"]}}

    want = transformer.abstract_params(mc)
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
