"""Whisper-style encoder-decoder. The conv/mel frontend is a STUB per the
brief: ``input_specs`` provides precomputed frame embeddings (B, enc_seq, d).

Adaptations: sinusoidal absolute embeddings on the encoder (as Whisper), RoPE
in the decoder self-attention (instead of Whisper's learned 448-entry table,
which cannot address the assigned 32k/500k decode shapes).

The decode cache keeps the decoder's self-attention keys and values as
(L, ...) stacks in ``attention.kv_order_for(hd)``, carried through the layer
scan and written in place, and each layer's cross-attention keys and values
over the encoder output (``ck``/``cv``, read only) as scanned inputs.
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
    dense,
    dtype_of,
    embed,
    embed_init,
    rmsnorm,
    rmsnorm_init,
    sinusoid_embed,
    swiglu,
    swiglu_init,
    unembed,
)


def _enc_layer_init(rng, cfg, dtype):
    ra, rm = jax.random.split(rng)
    return {
        "attn": attn_init(ra, cfg, dtype=dtype),
        "mlp": swiglu_init(rm, cfg.d_model, cfg.d_ff, dtype=dtype),
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
    }


def _dec_layer_init(rng, cfg, dtype):
    ra, rc, rm = jax.random.split(rng, 3)
    return {
        "self_attn": attn_init(ra, cfg, dtype=dtype),
        "cross_attn": attn_init(rc, cfg, dtype=dtype),
        "mlp": swiglu_init(rm, cfg.d_model, cfg.d_ff, dtype=dtype),
        "ln1": rmsnorm_init(cfg.d_model, dtype),
        "lnc": rmsnorm_init(cfg.d_model, dtype),
        "ln2": rmsnorm_init(cfg.d_model, dtype),
    }


def init(rng, cfg: ModelConfig):
    dtype = dtype_of(cfg)
    r_emb, r_enc, r_dec, r_un = jax.random.split(rng, 4)
    return {
        "embed": embed_init(r_emb, cfg.padded_vocab, cfg.d_model, dtype),
        "unembed": embed_init(r_un, cfg.padded_vocab, cfg.d_model, dtype),
        "enc_layers": stack_init(
            r_enc, cfg.encoder_layers,
            functools.partial(_enc_layer_init, cfg=cfg, dtype=dtype),
        ),
        "dec_layers": stack_init(
            r_dec, cfg.n_layers,
            functools.partial(_dec_layer_init, cfg=cfg, dtype=dtype),
        ),
        "enc_norm": rmsnorm_init(cfg.d_model, dtype),
        "final_norm": rmsnorm_init(cfg.d_model, dtype),
    }


def encode(params, cfg: ModelConfig, frames, *, remat=None):
    """frames: (B, T_enc, d) precomputed frame embeddings (frontend stub)."""
    T = frames.shape[1]
    x = frames + sinusoid_embed(T, cfg.d_model).astype(frames.dtype)
    positions = jnp.arange(T)

    def layer(lp, x):
        h, _ = attention(
            lp["attn"], rmsnorm(lp["ln1"], x, cfg.norm_eps), cfg,
            positions=positions, theta=0.0, causal=False,
        )
        x = x + h
        return x + swiglu(lp["mlp"], rmsnorm(lp["ln2"], x, cfg.norm_eps))

    layer = remat_wrap(layer, remat)

    def body(x, lp):
        return layer(lp, x), None

    x, _ = jax.lax.scan(body, x, params["enc_layers"])
    return rmsnorm(params["enc_norm"], x, cfg.norm_eps)


def _dec_layer(
    lp, x, cfg, *, positions, enc_kv=None, enc_out=None,
    cache=None, cache_pos=None, layer=None,
):
    h, kv = attention(
        lp["self_attn"], rmsnorm(lp["ln1"], x, cfg.norm_eps), cfg,
        positions=positions, theta=cfg.rope_theta,
        cache=cache, cache_pos=cache_pos, layer=layer,
    )
    x = x + h
    xc = rmsnorm(lp["lnc"], x, cfg.norm_eps)
    if enc_kv is None:
        K, hd = cfg.n_kv_heads, cfg.hd
        B, T = enc_out.shape[:2]
        ek = dense(lp["cross_attn"]["wk"], enc_out).reshape(B, T, K, hd)
        ev = dense(lp["cross_attn"]["wv"], enc_out).reshape(B, T, K, hd)
        enc_kv = (ek, ev)
    h, _ = attention(
        lp["cross_attn"], xc, cfg, positions=positions, theta=0.0,
        kv_override=enc_kv,
    )
    x = x + h
    x = x + swiglu(lp["mlp"], rmsnorm(lp["ln2"], x, cfg.norm_eps))
    return x, kv, enc_kv


def loss_fn(params, batch, cfg: ModelConfig, *, remat=None, use_kernels=False):
    enc_out = encode(params, cfg, batch["frames"], remat=remat)
    x = embed(params["embed"], batch["tokens"])
    S = x.shape[1]
    positions = jnp.arange(S)

    def layer(lp, x):
        x, _, _ = _dec_layer(lp, x, cfg, positions=positions, enc_out=enc_out)
        return x

    layer = remat_wrap(layer, remat)

    def body(x, lp):
        return layer(lp, x), None

    x, _ = jax.lax.scan(body, x, params["dec_layers"])
    h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["unembed"], h)
    ce = cross_entropy_loss(logits, batch["labels"])
    return ce, {"ce": ce, "aux": 0.0}


def prefill(params, batch, S_max: int, cfg: ModelConfig, *, use_kernels=False):
    enc_out = encode(params, cfg, batch["frames"])
    x = embed(params["embed"], batch["tokens"])
    S = x.shape[1]
    positions = jnp.arange(S)

    def body(x, lp):
        x, kv, enc_kv = _dec_layer(lp, x, cfg, positions=positions, enc_out=enc_out)
        return x, (to_stack_order(kv), enc_kv)

    x, (kvs, enc_kvs) = jax.lax.scan(body, x, params["dec_layers"])
    h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["unembed"], h[:, -1])
    k, v = pad_stacks(kvs, cfg.hd, S_max)
    return logits, {"k": k, "v": v, "ck": enc_kvs[0], "cv": enc_kvs[1],
                    "pos": jnp.int32(S)}


def decode_step(params, cache, batch, cfg: ModelConfig, *, use_kernels=False):
    x = embed(params["embed"], batch["token"][:, None])
    pos = cache["pos"]
    positions = pos[None]

    # the self-attention stacks ride in the carry, updated in place
    def body(carry, inp):
        x, k, v = carry
        lp, ck, cv, layer = inp
        x, kv, _ = _dec_layer(
            lp, x, cfg, positions=positions, enc_kv=(ck, cv),
            cache=KVCache(k, v), cache_pos=pos, layer=layer,
        )
        return (x, kv.k, kv.v), None

    (x, k, v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["dec_layers"], cache["ck"], cache["cv"], jnp.arange(cfg.n_layers)),
    )
    h = rmsnorm(params["final_norm"], x, cfg.norm_eps)
    logits = unembed(params["unembed"], h[:, 0])
    return logits, dict(cache, k=k, v=v, pos=pos + 1)


def init_cache(cfg: ModelConfig, B: int, S_max: int):
    dtype = dtype_of(cfg)
    L, K, hd = cfg.n_layers, cfg.n_kv_heads, cfg.hd
    stack = kv_stack_shape(L, B, S_max, K, hd)
    return {
        "k": jnp.zeros(stack, dtype),
        "v": jnp.zeros(stack, dtype),
        "ck": jnp.zeros((L, B, cfg.encoder_seq, K, hd), dtype),
        "cv": jnp.zeros((L, B, cfg.encoder_seq, K, hd), dtype),
        "pos": jnp.int32(0),
    }


def input_specs(cfg: ModelConfig, shape: ShapeSpec) -> dict:
    extra = None
    if shape.kind != "decode":
        extra = {
            "frames": jax.ShapeDtypeStruct(
                (shape.global_batch, cfg.encoder_seq, cfg.d_model), dtype_of(cfg)
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
