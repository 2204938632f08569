"""Decoder-only transformer LM assembly: dense, MoE, gemma3-style
local/global block pattern, and VLM (prefix patch embeddings).

Layer stacks are scanned (``lax.scan``) over stacked params for compile-time
O(1) in depth; gemma3 uses a nested scan over (blocks x [R local + 1 global]).

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
from .attention import KVCache, attention, attn_init, kv_order_for, kv_stack_shape
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
    cache_write_pos=None,
    kv_positions=None,
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
        cache_write_pos=cache_write_pos,
        kv_positions=kv_positions,
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
    if cfg.local_global_ratio:
        R = cfg.local_global_ratio
        G = cfg.n_layers // (R + 1)
        rl, rg = jax.random.split(r_layers)
        local = stack_init(rl, G * R, layer_fn)
        params["local_layers"] = jax.tree.map(
            lambda a: a.reshape(G, R, *a.shape[1:]), local
        )
        params["global_layers"] = stack_init(rg, G, layer_fn)
    else:
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
    """x: (B, S, d) embedded input. Returns (hidden, cache_arrays, aux)."""
    if cfg.local_global_ratio:
        R = cfg.local_global_ratio
        W = cfg.sliding_window
        g_theta = cfg.global_rope_theta or cfg.rope_theta

        def local_fn(lp, x):
            return _layer_apply(
                lp, x, cfg, positions=positions, theta=cfg.rope_theta,
                window=W, use_kernels=use_kernels,
            )

        def global_fn(lp, x):
            return _layer_apply(
                lp, x, cfg, positions=positions, theta=g_theta,
                window=None, use_kernels=use_kernels,
            )

        local_fn = remat_wrap(local_fn, remat)
        global_fn = remat_wrap(global_fn, remat)

        def block(x, bp):
            lps, gp = bp

            def inner(xc, lp):
                xc, kv, _ = local_fn(lp, xc)
                return xc, kv

            x, lkv = jax.lax.scan(inner, x, lps)
            x, gkv, _ = global_fn(gp, x)
            return x, (lkv, gkv)

        with jax.named_scope("layers"):
            x, (lkvs, gkvs) = jax.lax.scan(
                block, x, (params["local_layers"], params["global_layers"])
            )
        cache = {"local": lkvs, "global": gkvs} if want_cache else None
        return x, cache, 0.0

    def layer_fn(lp, x):
        return _layer_apply(
            lp, x, cfg, positions=positions, theta=cfg.rope_theta,
            window=cfg.sliding_window, use_kernels=use_kernels,
        )

    layer_fn = remat_wrap(layer_fn, remat)
    order = kv_order_for(cfg.hd)

    def body(carry, lp):
        x, aux = carry
        x, kv, a = layer_fn(lp, x)
        # the cache is stacked in the order the decode loop reads
        kv = KVCache(kv.k.transpose(order), kv.v.transpose(order)) if want_cache else None
        return (x, aux + a), kv

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
    uniform stack's cache holds k/v as (L, ...) stacks in
    ``kv_order_for(hd)``, padded to ``S_max`` positions."""
    x, n_prefix = _embed_inputs(params, cfg, batch)
    B, S = x.shape[0], x.shape[1]
    dtype = dtype_of(cfg)
    positions = jnp.arange(S)
    h, kvs, _ = _forward(
        params, cfg, x, positions, want_cache=True, use_kernels=use_kernels
    )
    h = _final_norm(params, cfg, h)
    logits = _logits(params, cfg, h[:, -1])

    if cfg.local_global_ratio:
        W = cfg.sliding_window
        lkv, gkv = kvs["local"], kvs["global"]
        # local layers keep only the trailing window (ring buffer)
        take = min(W, S)
        lk = lkv.k[..., S - take:, :, :]
        lv = lkv.v[..., S - take:, :, :]
        if take < W:
            pad = [(0, 0)] * lk.ndim
            pad[-3] = (0, W - take)
            lk, lv = jnp.pad(lk, pad), jnp.pad(lv, pad)
        ring_pos = jnp.where(
            jnp.arange(W) < take, jnp.arange(W) + (S - take), -1
        ).astype(jnp.int32)
        # global layers get a full-length cache buffer
        def grow(a):
            pad = [(0, 0)] * a.ndim
            pad[-3] = (0, S_max - S)
            return jnp.pad(a, pad)
        cache = {
            "lk": lk, "lv": lv, "ring_pos": ring_pos,
            "gk": grow(gkv.k), "gv": grow(gkv.v),
            "pos": jnp.int32(S),
        }
    else:
        seq = 1 + kv_order_for(cfg.hd).index(1)  # the stacks' sequence axis

        def grow(a):
            pad = [(0, 0)] * a.ndim
            pad[seq] = (0, S_max - S)
            return jnp.pad(a, pad)
        cache = {"k": grow(kvs.k), "v": grow(kvs.v), "pos": jnp.int32(S)}
    return logits, cache


def decode_step(params, cache, batch, cfg: ModelConfig, *, use_kernels=False):
    """One token for every sequence. batch: {"token": (B,)}."""
    tok = batch["token"]
    with jax.named_scope("embed"):
        x = embed(params["embed"], tok[:, None])
    pos = cache["pos"]
    positions = pos[None]

    if cfg.local_global_ratio:
        W = cfg.sliding_window
        g_theta = cfg.global_rope_theta or cfg.rope_theta
        wp = jnp.mod(pos, W)
        ring_pos = jax.lax.dynamic_update_slice(cache["ring_pos"], pos[None], (wp,))

        def block(x, bp):
            lps, lk, lv, gp, gk, gv = bp

            def inner(xc, inp):
                lp, k1, v1 = inp
                xc, kv, _ = _layer_apply(
                    lp, xc, cfg, positions=positions, theta=cfg.rope_theta,
                    window=W, cache=KVCache(k1, v1), cache_pos=pos,
                    cache_write_pos=wp, kv_positions=ring_pos,
                    use_kernels=use_kernels,
                )
                return xc, kv

            x, lkv = jax.lax.scan(inner, x, (lps, lk, lv))
            x, gkv, _ = _layer_apply(
                gp, x, cfg, positions=positions, theta=g_theta, window=None,
                cache=KVCache(gk, gv), cache_pos=pos, use_kernels=use_kernels,
            )
            return x, (lkv, gkv)

        with jax.named_scope("layers"):
            x, (lkvs, gkvs) = jax.lax.scan(
                block, x,
                (params["local_layers"], cache["lk"], cache["lv"],
                 params["global_layers"], cache["gk"], cache["gv"]),
            )
        new_cache = {
            "lk": lkvs.k, "lv": lkvs.v, "ring_pos": ring_pos,
            "gk": gkvs.k, "gv": gkvs.v, "pos": pos + 1,
        }
    else:
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
    K, hd = cfg.n_kv_heads, cfg.hd
    if cfg.local_global_ratio:
        R = cfg.local_global_ratio
        G = cfg.n_layers // (R + 1)
        W = cfg.sliding_window
        return {
            "lk": jnp.zeros((G, R, B, W, K, hd), dtype),
            "lv": jnp.zeros((G, R, B, W, K, hd), dtype),
            "ring_pos": jnp.full((W,), -1, jnp.int32),
            "gk": jnp.zeros((G, B, S_max, K, hd), dtype),
            "gv": jnp.zeros((G, B, S_max, K, hd), dtype),
            "pos": jnp.int32(0),
        }
    stack = kv_stack_shape(cfg.n_layers, B, S_max, K, hd)
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
