"""Decoder-only transformer LM assembly: dense, MoE, and VLM (prefix patch
embeddings). Every layer attends alike, within ``sliding_window`` if set.

The layer stack is scanned (``lax.scan``) over stacked params for
compile-time O(1) in depth.

Train, prefill and decode name their work with ``jax.named_scope`` from one
vocabulary, which profiler traces carry as each op's ``op_name`` path:
``embed``, ``layers`` (the layer scan), ``norm``, ``attn_proj``, ``kv_write``,
``attend``, ``mlp`` or ``moe``, and ``lm_head``; the Mamba2 mixer of the
hybrids (``mamba2.py``) adds ``ssm_proj`` (in/out projections and the gated
norm), ``ssm_conv``, ``ssm_scan`` (the prefill's chunked SSD) and
``ssm_state`` (the decode step's state update). Scopes are HLO metadata only;
the compiled program is the same without them.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig, ShapeSpec
from .attention import (
    KVCache,
    attention,
    attn_init,
    kv_stack_shape,
    pad_stacks,
    to_stack_order,
)
from .common import Model, remat_wrap, stack_init, token_specs
from .layers import (
    cross_entropy_loss,
    dense,
    dtype_of,
    embed,
    embed_init,
    norm as rmsnorm,
    rmsnorm_init,
    swiglu,
    swiglu_init,
    unembed,
)
from .moe import moe_ffn, moe_init

MOE_AUX_COEF = 0.01


# ---------------------------------------------------------------------------
# layer init / apply
# ---------------------------------------------------------------------------
def _layer_init(rng, cfg: ModelConfig, *, dtype):
    ra, rm = jax.random.split(rng)
    p = {
        "attn": attn_init(ra, cfg, dtype=dtype),
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
    }
    if cfg.family == "moe":
        p["moe"] = moe_init(rm, cfg, dtype=dtype)
    else:
        p["mlp"] = swiglu_init(rm, cfg.d_model, cfg.d_ff, dtype=dtype)
    return p


def _layer_apply(
    lp,
    x,
    cfg: ModelConfig,
    *,
    positions,
    theta: float,
    window: Optional[int],
    cache: Optional[KVCache] = None,
    cache_pos=None,
    layer=None,
    use_kernels: bool = False,
):
    """Pre-norm block. Returns (x, new_kv, aux)."""
    with jax.named_scope("norm"):
        xn = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    h, kv = attention(
        lp["attn"],
        xn,
        cfg,
        positions=positions,
        theta=theta,
        window=window,
        cache=cache,
        cache_pos=cache_pos,
        layer=layer,
        use_kernels=use_kernels,
    )
    x = x + h
    with jax.named_scope("norm"):
        y = rmsnorm(lp["ln2"], x, cfg.norm_eps)
    if cfg.family == "moe":
        with jax.named_scope("moe"):
            m, aux = moe_ffn(lp["moe"], y, cfg)
    else:
        with jax.named_scope("mlp"):
            m, aux = swiglu(lp["mlp"], y), 0.0
    return x + m, kv, aux


# ---------------------------------------------------------------------------
# init
# ---------------------------------------------------------------------------
def init(rng, cfg: ModelConfig):
    dtype = dtype_of(cfg)
    r_emb, r_layers, r_un = jax.random.split(rng, 3)
    params = {
        "embed": embed_init(r_emb, cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(r_un, cfg.padded_vocab, cfg.d_model, dtype)
    layer_fn = functools.partial(_layer_init, cfg=cfg, dtype=dtype)
    params["layers"] = stack_init(r_layers, cfg.n_layers, layer_fn)
    return params


# ---------------------------------------------------------------------------
# forward core (train / prefill share this)
# ---------------------------------------------------------------------------
def _forward(
    params,
    cfg: ModelConfig,
    x,
    positions,
    *,
    want_cache: bool,
    remat: Optional[str] = None,
    use_kernels: bool = False,
):
    """x: (B, S, d) embedded input. Returns (hidden, every layer's K/V as
    (L, ...) stacks in ``kv_order_for(hd)`` if ``want_cache``, aux)."""

    def layer_fn(lp, x):
        return _layer_apply(
            lp, x, cfg, positions=positions, theta=cfg.rope_theta,
            window=cfg.sliding_window, use_kernels=use_kernels,
        )

    layer_fn = remat_wrap(layer_fn, remat)

    def body(carry, lp):
        x, aux = carry
        x, kv, a = layer_fn(lp, x)
        return (x, aux + a), (to_stack_order(kv) if want_cache else None)

    with jax.named_scope("layers"):
        (x, aux), kvs = jax.lax.scan(body, (x, 0.0), params["layers"])
    return x, kvs, aux


def _embed_inputs(params, cfg: ModelConfig, batch):
    """Token embedding (+ VLM patch-prefix concat). Returns (x, n_prefix)."""
    with jax.named_scope("embed"):
        x = embed(params["embed"], batch["tokens"])
        if cfg.d_model and cfg.family == "vlm" and "patch_embeds" in batch:
            x = jnp.concatenate([batch["patch_embeds"].astype(x.dtype), x], axis=1)
            return x, batch["patch_embeds"].shape[1]
    return x, 0


def _logits(params, cfg: ModelConfig, h):
    p = params.get("unembed", params["embed"])
    with jax.named_scope("lm_head"):
        return unembed(p, h)


def _final_norm(params, cfg: ModelConfig, h):
    with jax.named_scope("norm"):
        return rmsnorm(params["final_norm"], h, cfg.norm_eps)


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------
def loss_fn(params, batch, cfg: ModelConfig, *, remat=None, use_kernels=False):
    x, n_prefix = _embed_inputs(params, cfg, batch)
    B, S = x.shape[0], x.shape[1]
    positions = jnp.arange(S)
    h, _, aux = _forward(
        params, cfg, x, positions, want_cache=False, remat=remat,
        use_kernels=use_kernels,
    )
    h = _final_norm(params, cfg, h)
    if n_prefix:
        h = h[:, n_prefix:]
    logits = _logits(params, cfg, h)
    ce = cross_entropy_loss(logits, batch["labels"])
    total = ce + MOE_AUX_COEF * aux
    return total, {"ce": ce, "aux": aux}


def prefill(params, batch, S_max: int, cfg: ModelConfig, *, use_kernels=False):
    """Run the prompt, return (last-position logits, decode cache). The
    cache holds k/v as (L, ...) stacks in ``kv_order_for(hd)``, padded to
    ``S_max`` positions."""
    x, _ = _embed_inputs(params, cfg, batch)
    S = x.shape[1]
    h, kvs, _ = _forward(
        params, cfg, x, jnp.arange(S), want_cache=True, use_kernels=use_kernels
    )
    h = _final_norm(params, cfg, h)
    logits = _logits(params, cfg, h[:, -1])
    k, v = pad_stacks(kvs, cfg.hd, S_max)
    return logits, {"k": k, "v": v, "pos": jnp.int32(S)}


def decode_step(params, cache, batch, cfg: ModelConfig, *, use_kernels=False):
    """One token for every sequence. batch: {"token": (B,)}."""
    tok = batch["token"]
    with jax.named_scope("embed"):
        x = embed(params["embed"], tok[:, None])
    pos = cache["pos"]
    positions = pos[None]

    # the cache stacks ride in the carry and are updated in place; a scan
    # over them as xs/ys would slice and restack them whole every step
    def body(carry, inp):
        x, _, ks, vs = carry
        lp, layer = inp
        x, kv, a = _layer_apply(
            lp, x, cfg, positions=positions, theta=cfg.rope_theta,
            window=cfg.sliding_window, cache=KVCache(ks, vs),
            cache_pos=pos, layer=layer, use_kernels=use_kernels,
        )
        return (x, a, kv.k, kv.v), None

    with jax.named_scope("layers"):
        (x, _, k, v), _ = jax.lax.scan(
            body, (x, 0.0, cache["k"], cache["v"]),
            (params["layers"], jnp.arange(cfg.n_layers)),
        )
    new_cache = {"k": k, "v": v, "pos": pos + 1}

    h = _final_norm(params, cfg, x)
    logits = _logits(params, cfg, h[:, 0])
    return logits, new_cache


def init_cache(cfg: ModelConfig, B: int, S_max: int):
    """An empty decode cache, laid out as ``prefill`` returns it."""
    dtype = dtype_of(cfg)
    stack = kv_stack_shape(cfg.n_layers, B, S_max, cfg.n_kv_heads, cfg.hd)
    return {
        "k": jnp.zeros(stack, dtype),
        "v": jnp.zeros(stack, dtype),
        "pos": jnp.int32(0),
    }


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    extra = None
    if cfg.family == "vlm" and shape.kind != "decode":
        extra = {
            "patch_embeds": jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.n_patches, cfg.d_model), dtype_of(cfg)
            )
        }
    return token_specs(shape, extra)


def build(cfg: ModelConfig) -> Model:
    return Model(
        cfg=cfg,
        init=functools.partial(init, cfg=cfg),
        loss=functools.partial(loss_fn, cfg=cfg),
        prefill=functools.partial(prefill, cfg=cfg),
        decode_step=functools.partial(decode_step, cfg=cfg),
        init_cache=functools.partial(init_cache, cfg),
        input_specs=functools.partial(input_specs, cfg),
    )
