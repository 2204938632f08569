"""Mamba2 hybrids, sharing one mixer (``mamba2.mamba_forward``) and one
decode KV cache design: (L, ...) key and value stacks in
``attention.kv_order_for(hd)``, one entry per attention site, carried
through the layer scans and written in place (``attention(..., layer=...)``).

Zamba2-style (``shared_attn_every``): Mamba2 backbone with a single
weight-shared attention+MLP block applied after every ``shared_attn_every``
mamba layers. Unlike the released Zamba2, the shared block consumes the
residual stream directly (no concat with the original embedding, no
per-invocation LoRA). Each invocation site keeps its own K/V: entry g of the
``k``/``v`` stacks, which the group scan carries and indexes by group. The
mamba layers' conv windows and states pass through the nested scans as
inputs and outputs.

Granite-4.0-H-style (``layer_types``, HF ``GraniteMoeHybrid`` without
experts): every layer is a pre-norm mixer, Mamba2 or GQA attention (scale
``attention_multiplier``, NoPE where ``nope``), then a pre-norm SwiGLU MLP;
each sublayer's output is scaled by ``residual_multiplier`` before it joins
the residual stream, the embeddings by ``embedding_multiplier``, and the
logits divided by ``logits_scaling``. The pattern is ``groups`` repeats of
one period holding one attention layer. The decode cache is four stacks:
the mamba layers' conv windows (n_mamba, W-1, B, Ch) and float32 states
(n_mamba, B, H, P, N), and the attention layers' keys and values
(n_attn, B, K, hd, S_max), ``attention.kv_order_for``'s ``SEQ_MINOR`` for
heads of 64. They ride in the layer scans' carry, and each layer reads and
writes its own slice in place.
Scopes as in ``transformer.py``, with the mixer's ``ssm_*`` names.
"""

from __future__ import annotations

import functools

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
    dtype_of,
    embed,
    embed_init,
    rmsnorm,
    rmsnorm_init,
    swiglu,
    swiglu_init,
    unembed,
)
from .mamba2 import MambaCache, empty_mamba_cache, mamba_forward, mamba_init


def _groups(cfg: ModelConfig) -> tuple[int, int, int]:
    gs = cfg.shared_attn_every
    ng = cfg.n_layers // gs
    tail = cfg.n_layers - ng * gs
    return ng, gs, tail


def _mamba_layer_init(rng, cfg, dtype):
    return {
        "norm": rmsnorm_init(cfg.d_model, dtype),
        "mamba": mamba_init(rng, cfg, dtype=dtype),
    }


def _mamba_layer(lp, x, cfg, cache=None, use_kernels=False):
    h, new_cache = mamba_forward(
        lp["mamba"], rmsnorm(lp["norm"], x, cfg.norm_eps), cfg, cache=cache,
        use_kernels=use_kernels,
    )
    return x + h, new_cache


def _shared_apply(sp, x, cfg, *, positions, cache=None, cache_pos=None, layer=None):
    h, kv = attention(
        sp["attn"], rmsnorm(sp["ln1"], x, cfg.norm_eps), cfg,
        positions=positions, theta=cfg.rope_theta,
        cache=cache, cache_pos=cache_pos, layer=layer,
    )
    x = x + h
    x = x + swiglu(sp["mlp"], rmsnorm(sp["ln2"], x, cfg.norm_eps))
    return x, kv


def init(rng, cfg: ModelConfig):
    dtype = dtype_of(cfg)
    ng, gs, tail = _groups(cfg)
    r_emb, r_m, r_t, r_s, r_un = jax.random.split(rng, 5)
    layer_fn = functools.partial(_mamba_layer_init, cfg=cfg, dtype=dtype)
    grouped = stack_init(r_m, ng * gs, layer_fn)
    params = {
        "embed": embed_init(r_emb, cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
        "mamba_groups": jax.tree.map(
            lambda a: a.reshape(ng, gs, *a.shape[1:]), grouped
        ),
        "shared": {
            "attn": attn_init(r_s, cfg, dtype=dtype),
            "mlp": swiglu_init(jax.random.fold_in(r_s, 1), cfg.d_model, cfg.d_ff, dtype=dtype),
            "ln1": rmsnorm_init(cfg.d_model, dtype),
            "ln2": rmsnorm_init(cfg.d_model, dtype),
        },
    }
    if tail:
        params["mamba_tail"] = stack_init(r_t, tail, layer_fn)
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(r_un, cfg.padded_vocab, cfg.d_model, dtype)
    return params


def _forward(params, cfg, x, positions, *, remat=None, use_kernels=False):
    """The hidden states of a whole sequence, with no cache."""
    shared = params["shared"]
    m_layer = remat_wrap(
        functools.partial(_mamba_layer, cfg=cfg, use_kernels=use_kernels), remat
    )

    def inner(xc, lp):
        return m_layer(lp, xc)[0], None

    def group(x, gp):
        x, _ = jax.lax.scan(inner, x, gp)
        return _shared_apply(shared, x, cfg, positions=positions)[0], None

    x, _ = jax.lax.scan(group, x, params["mamba_groups"])
    if "mamba_tail" in params:
        x, _ = jax.lax.scan(inner, x, params["mamba_tail"])
    return x


def loss_fn(params, batch, cfg: ModelConfig, *, remat=None, use_kernels=False):
    x = embed(params["embed"], batch["tokens"])
    S = x.shape[1]
    h = _forward(params, cfg, x, jnp.arange(S), remat=remat, use_kernels=use_kernels)
    h = rmsnorm(params["final_norm"], h, cfg.norm_eps)
    logits = unembed(params.get("unembed", params["embed"]), h)
    ce = cross_entropy_loss(logits, batch["labels"])
    return ce, {"ce": ce, "aux": 0.0}


def prefill(params, batch, S_max: int, cfg: ModelConfig, *, use_kernels=False):
    """Run the prompt, keeping each mamba layer's final conv window and state
    and the shared block's K/V at every invocation site, as (ng, ...) stacks
    padded to ``S_max`` positions."""
    x = embed(params["embed"], batch["tokens"])
    S = x.shape[1]
    positions = jnp.arange(S)
    shared = params["shared"]

    def inner(xc, lp):
        return _mamba_layer(lp, xc, cfg, use_kernels=use_kernels)

    def group(x, gp):
        x, states = jax.lax.scan(inner, x, gp)
        x, kv = _shared_apply(shared, x, cfg, positions=positions)
        return x, (states, to_stack_order(kv))

    x, (g_states, kvs) = jax.lax.scan(group, x, params["mamba_groups"])
    t_states = None
    if "mamba_tail" in params:
        x, t_states = jax.lax.scan(inner, x, params["mamba_tail"])

    h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params.get("unembed", params["embed"]), h[:, -1])

    k, v = pad_stacks(kvs, cfg.hd, S_max)
    cache = {
        "g_conv": g_states.conv, "g_h": g_states.h, "k": k, "v": v,
        "pos": jnp.int32(S),
    }
    if t_states is not None:
        cache["t_conv"], cache["t_h"] = t_states.conv, t_states.h
    return logits, cache


def decode_step(params, cache, batch, cfg: ModelConfig, *, use_kernels=False):
    x = embed(params["embed"], batch["token"][:, None])
    pos = cache["pos"]
    positions = pos[None]
    shared = params["shared"]

    def m_step(xc, inp):
        lp, c, hh = inp
        return _mamba_layer(lp, xc, cfg, cache=MambaCache(c, hh))

    # the K/V stacks ride in the group scan's carry, updated in place
    def group(carry, inp):
        x, k, v = carry
        lps, conv, h, g = inp
        x, states = jax.lax.scan(m_step, x, (lps, conv, h))
        x, kv = _shared_apply(shared, x, cfg, positions=positions,
                              cache=KVCache(k, v), cache_pos=pos, layer=g)
        return (x, kv.k, kv.v), states

    ng = _groups(cfg)[0]
    (x, k, v), g_states = jax.lax.scan(
        group, (x, cache["k"], cache["v"]),
        (params["mamba_groups"], cache["g_conv"], cache["g_h"], jnp.arange(ng)),
    )
    new_cache = {
        "g_conv": g_states.conv, "g_h": g_states.h, "k": k, "v": v, "pos": pos + 1,
    }
    if "mamba_tail" in params:
        x, t_states = jax.lax.scan(
            m_step, x, (params["mamba_tail"], cache["t_conv"], cache["t_h"])
        )
        new_cache["t_conv"], new_cache["t_h"] = t_states.conv, t_states.h

    h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params.get("unembed", params["embed"]), h[:, 0])
    return logits, new_cache


def init_cache(cfg: ModelConfig, B: int, S_max: int):
    dtype = dtype_of(cfg)
    ng, gs, tail = _groups(cfg)
    mc = empty_mamba_cache(cfg, B, dtype)

    def rep(a, n):
        return jnp.broadcast_to(a, (n,) + a.shape).copy() if n else None

    def rep2(a):
        return jnp.broadcast_to(a, (ng, gs) + a.shape).copy()

    kv = kv_stack_shape(ng, B, S_max, cfg.n_kv_heads, cfg.hd)
    cache = {
        "g_conv": rep2(mc.conv), "g_h": rep2(mc.h),
        "k": jnp.zeros(kv, dtype), "v": jnp.zeros(kv, dtype),
        "pos": jnp.int32(0),
    }
    if tail:
        cache["t_conv"] = rep(mc.conv, tail)
        cache["t_h"] = rep(mc.h, tail)
    return cache


# ---------------------------------------------------------------------------
# granite-4.0-h: a pattern of mamba2 and attention layers, each with an MLP
# ---------------------------------------------------------------------------
def _pattern(cfg: ModelConfig) -> tuple[int, int, int]:
    """(groups, mamba layers before each group's attention layer, after it)."""
    types = tuple(cfg.layer_types)
    G = types.count("attention")
    pre = types.index("attention") if G else 0
    post = len(types) // max(G, 1) - pre - 1
    if not G or ((("mamba",) * pre + ("attention",) + ("mamba",) * post) * G) != types:
        raise ValueError(f"{cfg.name}: layer_types is not a repeated period with "
                         "one attention layer")
    return G, pre, post


def _gh_layer_init(rng, cfg: ModelConfig, dtype, mixer: str):
    rm, rf = jax.random.split(rng)
    p = {
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
        "mlp": swiglu_init(rf, cfg.d_model, cfg.d_ff, dtype=dtype),
    }
    if mixer == "mamba":
        p["mamba"] = mamba_init(rm, cfg, dtype=dtype)
    else:
        p["attn"] = attn_init(rm, cfg, dtype=dtype)
    return p


def _gh_init(rng, cfg: ModelConfig):
    dtype = dtype_of(cfg)
    G = _pattern(cfg)[0]
    r_emb, r_m, r_a, r_un = jax.random.split(rng, 4)
    params = {
        "embed": embed_init(r_emb, cfg.padded_vocab, cfg.d_model, dtype),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
        "mamba_layers": stack_init(r_m, cfg.n_layers - G, functools.partial(
            _gh_layer_init, cfg=cfg, dtype=dtype, mixer="mamba")),
        "attn_layers": stack_init(r_a, G, functools.partial(
            _gh_layer_init, cfg=cfg, dtype=dtype, mixer="attention")),
    }
    if not cfg.tie_embeddings:
        params["unembed"] = embed_init(r_un, cfg.padded_vocab, cfg.d_model, dtype)
    return params


def _gh_embed(params, tokens, cfg: ModelConfig):
    with jax.named_scope("embed"):
        return embed(params["embed"], tokens) * cfg.embedding_multiplier


def _gh_logits(params, x, cfg: ModelConfig):
    with jax.named_scope("norm"):
        h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    with jax.named_scope("lm_head"):
        return unembed(params.get("unembed", params["embed"]), h) / cfg.logits_scaling


def _gh_mlp(lp, x, cfg: ModelConfig):
    with jax.named_scope("norm"):
        y = rmsnorm(lp["ln2"], x, cfg.norm_eps)
    with jax.named_scope("mlp"):
        return x + swiglu(lp["mlp"], y) * cfg.residual_multiplier


def _gh_mamba(lp, x, cfg: ModelConfig, cache=None, use_kernels=False):
    """One mamba layer and its MLP; returns (x, MambaCache)."""
    with jax.named_scope("norm"):
        xn = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    h, state = mamba_forward(lp["mamba"], xn, cfg, cache=cache, use_kernels=use_kernels)
    return _gh_mlp(lp, x + h * cfg.residual_multiplier, cfg), state


def _gh_attn(lp, x, cfg: ModelConfig, *, positions, cache=None, cache_pos=None,
             layer=None, use_kernels=False):
    """One attention layer and its MLP; returns (x, KVCache)."""
    with jax.named_scope("norm"):
        xn = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    h, kv = attention(
        lp["attn"], xn, cfg, positions=positions,
        theta=0.0 if cfg.nope else cfg.rope_theta, scale=cfg.attention_multiplier,
        cache=cache, cache_pos=cache_pos, layer=layer, use_kernels=use_kernels,
    )
    return _gh_mlp(lp, x + h * cfg.residual_multiplier, cfg), kv


def _take(tree, i):
    return jax.tree.map(lambda a: jax.lax.dynamic_index_in_dim(a, i, keepdims=False), tree)


def _put(stack, i, a):
    """``stack`` with slice ``i`` replaced by ``a``, in place."""
    return jax.lax.dynamic_update_slice(stack, a[None].astype(stack.dtype),
                                        (i,) + (0,) * a.ndim)


def _gh_layers(params, x, m_state, a_state, cfg: ModelConfig, mamba_fn, attn_fn):
    """The layers in pattern order: a scan over the groups, and in each the
    mamba layers before its attention layer, the attention layer, and the
    mamba layers after. ``mamba_fn(lp, x, m_state, m)`` and
    ``attn_fn(lp, x, a_state, g)`` return (x, their state). The mamba
    layers' state rides in every scan's carry, the attention layers' in the
    group scan's only."""
    G, pre, post = _pattern(cfg)

    def m_body(carry, m):
        x, st = carry
        return mamba_fn(_take(params["mamba_layers"], m), x, st, m), None

    def g_body(carry, g):
        x, m_st, a_st = carry
        first = g * (pre + post)
        (x, m_st), _ = jax.lax.scan(m_body, (x, m_st), first + jnp.arange(pre))
        x, a_st = attn_fn(_take(params["attn_layers"], g), x, a_st, g)
        (x, m_st), _ = jax.lax.scan(m_body, (x, m_st), first + pre + jnp.arange(post))
        return (x, m_st, a_st), None

    with jax.named_scope("layers"):
        (x, m_state, a_state), _ = jax.lax.scan(g_body, (x, m_state, a_state),
                                                jnp.arange(G))
    return x, m_state, a_state


def granite_forward(params, tokens, cfg: ModelConfig, *, remat=None, use_kernels=False):
    """Logits at every position of ``tokens`` (B, S), with no cache."""
    x = _gh_embed(params, tokens, cfg)
    positions = jnp.arange(x.shape[1])
    mamba = remat_wrap(
        lambda lp, x: _gh_mamba(lp, x, cfg, use_kernels=use_kernels)[0], remat)
    attn = remat_wrap(
        lambda lp, x: _gh_attn(lp, x, cfg, positions=positions,
                               use_kernels=use_kernels)[0], remat)
    x, _, _ = _gh_layers(params, x, (), (), cfg,
                         lambda lp, x, st, m: (mamba(lp, x), st),
                         lambda lp, x, st, g: (attn(lp, x), st))
    return _gh_logits(params, x, cfg)


def _gh_loss(params, batch, cfg: ModelConfig, *, remat=None, use_kernels=False):
    logits = granite_forward(params, batch["tokens"], cfg, remat=remat,
                             use_kernels=use_kernels)
    ce = cross_entropy_loss(logits, batch["labels"])
    return ce, {"ce": ce, "aux": 0.0}


def _gh_prefill(params, batch, S_max: int, cfg: ModelConfig, *, use_kernels=False):
    """Run the prompt; the cache holds every layer's final conv window and
    state, and the attention layers' K/V in the decode loop's layout."""
    x = _gh_embed(params, batch["tokens"], cfg)
    B, S = x.shape[:2]
    positions = jnp.arange(S)
    c = _gh_init_cache(cfg, B, S_max)

    def mamba_fn(lp, x, st, m):
        conv, h = st
        x, ms = _gh_mamba(lp, x, cfg, use_kernels=use_kernels)
        with jax.named_scope("ssm_conv"):
            conv = _put(conv, m, ms.conv.swapaxes(0, 1))
        with jax.named_scope("ssm_scan"):
            h = _put(h, m, ms.h)
        return x, (conv, h)

    def attn_fn(lp, x, st, g):
        k, v = st
        x, kv = _gh_attn(lp, x, cfg, positions=positions, use_kernels=use_kernels)
        with jax.named_scope("kv_write"):
            kv = to_stack_order(kv)
            k, v = _put(k, g, kv.k), _put(v, g, kv.v)
        return x, (k, v)

    x, (conv, h), (k, v) = _gh_layers(params, x, (c["conv"], c["h"]), (c["k"], c["v"]),
                                      cfg, mamba_fn, attn_fn)
    logits = _gh_logits(params, x[:, -1], cfg)
    return logits, {"conv": conv, "h": h, "k": k, "v": v, "pos": jnp.int32(S)}


def _gh_decode(params, cache, batch, cfg: ModelConfig, *, use_kernels=False):
    """One token for every sequence; each layer updates its slice of the
    carried stacks in place."""
    x = _gh_embed(params, batch["token"][:, None], cfg)
    pos = cache["pos"]
    positions = pos[None]

    def mamba_fn(lp, x, st, m):
        conv, h = st
        with jax.named_scope("ssm_conv"):
            c = jax.lax.dynamic_index_in_dim(conv, m, keepdims=False).swapaxes(0, 1)
        with jax.named_scope("ssm_state"):
            hm = jax.lax.dynamic_index_in_dim(h, m, keepdims=False)
        x, ms = _gh_mamba(lp, x, cfg, cache=MambaCache(c, hm), use_kernels=use_kernels)
        with jax.named_scope("ssm_conv"):
            conv = _put(conv, m, ms.conv.swapaxes(0, 1))
        with jax.named_scope("ssm_state"):
            h = _put(h, m, ms.h)
        return x, (conv, h)

    def attn_fn(lp, x, st, g):
        x, kv = _gh_attn(lp, x, cfg, positions=positions, cache=KVCache(*st),
                         cache_pos=pos, layer=g, use_kernels=use_kernels)
        return x, tuple(kv)

    x, (conv, h), (k, v) = _gh_layers(
        params, x, (cache["conv"], cache["h"]), (cache["k"], cache["v"]), cfg,
        mamba_fn, attn_fn)
    logits = _gh_logits(params, x[:, 0], cfg)
    return logits, {"conv": conv, "h": h, "k": k, "v": v, "pos": pos + 1}


def _gh_init_cache(cfg: ModelConfig, B: int, S_max: int):
    G = _pattern(cfg)[0]
    mc = empty_mamba_cache(cfg, B, dtype_of(cfg))
    kv = kv_stack_shape(G, B, S_max, cfg.n_kv_heads, cfg.hd)
    return {
        "conv": jnp.zeros((cfg.n_layers - G,) + mc.conv.swapaxes(0, 1).shape, mc.conv.dtype),
        "h": jnp.zeros((cfg.n_layers - G,) + mc.h.shape, mc.h.dtype),
        "k": jnp.zeros(kv, dtype_of(cfg)),
        "v": jnp.zeros(kv, dtype_of(cfg)),
        "pos": jnp.int32(0),
    }


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    return token_specs(shape)


def build(cfg: ModelConfig) -> Model:
    if cfg.layer_types:
        fns = (_gh_init, _gh_loss, _gh_prefill, _gh_decode, _gh_init_cache)
    else:
        fns = (init, loss_fn, prefill, decode_step, init_cache)
    i, lo, pf, dec, ic = fns
    return Model(
        cfg=cfg,
        init=functools.partial(i, cfg=cfg),
        loss=functools.partial(lo, cfg=cfg),
        prefill=functools.partial(pf, cfg=cfg),
        decode_step=functools.partial(dec, cfg=cfg),
        init_cache=functools.partial(ic, cfg),
        input_specs=functools.partial(input_specs, cfg),
    )
