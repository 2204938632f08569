"""GQA attention with causal/sliding-window masks, and the decode KV cache.

The decode cache has one design, for every family: each k and v is one
(L, ...) stack of all attention layers (or sites) in ``kv_order_for(hd)``,
which the decode loop carries through its layer scan and ``attention(...,
layer=...)`` writes and reads in place. This module owns its layout: the
order, the stacks' shape (``kv_stack_shape``) and axes (``kv_axes``), how a
prefill builds them (``to_stack_order``, ``pad_stacks``) and how a decode
step writes one position (``_write_position``).

The compute-heavy paths dispatch to Pallas kernels (``repro.kernels.ops``)
when ``use_kernels`` is on; the pure-jnp path here is the oracle and the
CPU/dry-run path.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import apply_rope, dense, dense_init, head_rmsnorm, rope_tables

_NEG = -2.0e38

# Orders of the decode cache stacks' axes after the layer axis, as
# permutations of one layer's (B, S, K, hd) keys or values; ``kv_order_for``
# picks one from the head width. HEAD_ROWS, (B, K, S, hd), is for heads that
# fill the TPU's lanes: each (b, k) pair's keys are S_max rows of hd lanes,
# which the decode step's dots read in place. SEQ_MINOR, (B, K, hd, S), puts
# the sequence on the lanes for narrower heads. Either way the v5e compiler
# keeps the stacks in their own layout through the decode loop only where the
# sequence sits on the tiled axes and each step rewrites an aligned block
# (``_write_position``); otherwise it copies both stacks whole into another
# layout and back at every step (heads of 64 sequence-major, or heads of 128
# in SEQ_MINOR).
HEAD_ROWS = (0, 2, 1, 3)
SEQ_MINOR = (0, 2, 3, 1)
LANES = 128
SUBLANES = 8


def kv_order_for(hd: int) -> tuple:
    """The stacks' order for heads of ``hd``."""
    return HEAD_ROWS if hd % LANES == 0 else SEQ_MINOR


def kv_stack_shape(n: int, B: int, S_max: int, K: int, hd: int) -> tuple:
    """Shape of ``n`` layers' keys (or values) in ``kv_order_for(hd)``."""
    return (n,) + tuple((B, S_max, K, hd)[a] for a in kv_order_for(hd))


class KVAxes(NamedTuple):
    """Where an (L, ...) stack of heads of one width keeps its batch,
    sequence and head axes."""

    batch: int
    seq: int
    heads: int


def kv_axes(hd: int) -> KVAxes:
    """The axes of an (L, ...) stack in ``kv_order_for(hd)``."""
    order = kv_order_for(hd)
    return KVAxes(*(1 + order.index(a) for a in range(3)))


class KVCache(NamedTuple):
    """Keys and values. A prefill's ``attention`` returns one layer's, each
    (B, S, K, hd); the decode cache holds every layer's as a pair of (L, ...)
    stacks in ``kv_order_for(hd)`` (``to_stack_order``, ``pad_stacks``),
    which the decode loop carries and ``attention(..., layer=...)`` reads and
    writes in place."""

    k: jnp.ndarray
    v: jnp.ndarray


def to_stack_order(kv: KVCache) -> KVCache:
    """One layer's keys and values, (B, S, K, hd) each, in ``kv_order_for(hd)``:
    what a prefill's layer scan emits, so that it returns (L, ...) stacks."""
    order = kv_order_for(kv.k.shape[-1])
    return KVCache(kv.k.transpose(order), kv.v.transpose(order))


def pad_stacks(kv: KVCache, hd: int, S_max: int) -> KVCache:
    """(L, ...) stacks of heads of ``hd`` in ``kv_order_for(hd)``, the
    sequence padded to ``S_max`` positions: the decode cache a prefill
    returns."""
    seq = kv_axes(hd).seq

    def grow(a):
        pad = [(0, 0)] * a.ndim
        pad[seq] = (0, S_max - a.shape[seq])
        return jnp.pad(a, pad)

    return KVCache(grow(kv.k), grow(kv.v))


def attn_init(rng, cfg: ModelConfig, *, dtype=jnp.float32):
    rq, rk, rv, ro, rn = jax.random.split(rng, 5)
    d, hd = cfg.d_model, cfg.hd
    p = {
        "wq": dense_init(rq, d, cfg.n_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wk": dense_init(rk, d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wv": dense_init(rv, d, cfg.n_kv_heads * hd, bias=cfg.qkv_bias, dtype=dtype),
        "wo": dense_init(ro, cfg.n_heads * hd, d, dtype=dtype),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.ones((hd,), dtype=dtype)
        p["k_norm"] = jnp.ones((hd,), dtype=dtype)
    return p


def _mask(
    S_q: int,
    S_k: int,
    *,
    causal: bool,
    window: Optional[int],
    q_offset,
    kv_len=None,
):
    """(S_q, S_k) additive mask. ``q_offset``: absolute position of query row 0
    (static int or traced scalar). ``kv_len``: valid prefix of the key axis."""
    rows = jnp.arange(S_q)[:, None] + q_offset
    cols = jnp.arange(S_k)[None, :]
    ok = jnp.ones((S_q, S_k), dtype=bool)
    if causal:
        ok &= cols <= rows
    if window is not None:
        ok &= cols > rows - window
    if kv_len is not None:
        ok &= jnp.arange(S_k)[None, :] < kv_len
    return jnp.where(ok, 0.0, _NEG)


def sdpa(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset=0,
    kv_len=None,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Reference GQA attention. q: (B,S,H,hd); k/v: (B,T,K,hd); H % K == 0.
    ``scale`` multiplies the scores (default 1/sqrt(hd))."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qg = q.reshape(B, S, K, G, hd)
    scale = hd ** -0.5 if scale is None else scale
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k).astype(jnp.float32) * scale
    logits = logits + _mask(
        S, T, causal=causal, window=window, q_offset=q_offset, kv_len=kv_len,
    )
    w = jax.nn.softmax(logits, axis=-1).astype(v.dtype)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v)
    return out.reshape(B, S, H, hd)


# blockwise path kicks in at this many KV positions (memory: never
# materialize (S, T) score matrices at 4k+; the Pallas kernel is the TPU
# equivalent, this is the XLA-lowerable one used by dry-runs and grads)
BLOCKWISE_THRESHOLD = 2048


def blockwise_sdpa(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    q_offset=0,
    q_chunk: int = 512,
    k_chunk: int = 1024,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Flash-style online-softmax attention in pure JAX (lax.scan over KV
    blocks, outer scan over Q blocks). O(S*hd) memory instead of O(S*T)."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    qc = min(q_chunk, S)
    kc = min(k_chunk, T)
    if S % qc or T % kc:
        return sdpa(q, k, v, causal=causal, window=window, q_offset=q_offset, scale=scale)
    nq, nk = S // qc, T // kc
    scale = hd ** -0.5 if scale is None else scale
    f32 = jnp.float32

    kb = k.reshape(B, nk, kc, K, hd)
    vb = v.reshape(B, nk, kc, K, hd)

    def one_q_block(carry, inp):
        qi, qblk = inp                        # scalar, (B, qc, H, hd)
        qg = qblk.reshape(B, qc, K, G, hd)
        rows = q_offset + qi * qc + jnp.arange(qc)[:, None]

        def kv_body(st, kin):
            ki, kcur, vcur = kin
            m, l, acc = st
            s = jnp.einsum("bskgd,btkd->bkgst", qg, kcur).astype(f32) * scale
            cols = ki * kc + jnp.arange(kc)[None, :]
            ok = jnp.ones((qc, kc), bool)
            if causal:
                ok &= cols <= rows
            if window is not None:
                ok &= cols > rows - window
            s = jnp.where(ok, s, _NEG)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            alpha = jnp.exp(m - m_new)
            l = alpha * l + p.sum(-1)
            acc = alpha[..., None] * acc + jnp.einsum(
                "bkgst,btkd->bkgsd", p, vcur.astype(f32)
            )
            return (m_new, l, acc), None

        m0 = jnp.full((B, K, G, qc), -jnp.inf, f32)
        l0 = jnp.zeros((B, K, G, qc), f32)
        a0 = jnp.zeros((B, K, G, qc, hd), f32)
        (m, l, acc), _ = jax.lax.scan(
            kv_body, (m0, l0, a0),
            (jnp.arange(nk), kb.transpose(1, 0, 2, 3, 4), vb.transpose(1, 0, 2, 3, 4)),
        )
        out = acc / jnp.where(l == 0.0, 1.0, l)[..., None]
        out = out.transpose(0, 3, 1, 2, 4).reshape(B, qc, H, hd)
        return carry, out.astype(q.dtype)

    qb = q.reshape(B, nq, qc, H, hd).transpose(1, 0, 2, 3, 4)
    _, outs = jax.lax.scan(one_q_block, (), (jnp.arange(nq), qb))
    return outs.transpose(1, 0, 2, 3, 4).reshape(B, S, H, hd)


def attention(
    p,
    x: jnp.ndarray,
    cfg: ModelConfig,
    *,
    positions: jnp.ndarray,
    theta: float,
    causal: bool = True,
    window: Optional[int] = None,
    cache: Optional[KVCache] = None,
    cache_pos=None,
    kv_override: Optional[tuple] = None,
    layer=None,
    scale: Optional[float] = None,
    use_kernels: bool = False,
):
    """Full attention sub-layer: qkv proj -> rope -> sdpa -> out proj.

    Modes:
      * train/prefill: ``cache is None`` -> attends within x; returns
        (out, KVCache(k, v)) so prefill can keep the cache.
      * decode: ``cache`` holds every layer's (L, ...) stacks in
        ``kv_order_for(hd)`` and ``layer`` (a traced index) names this one;
        x is (B, 1, d). Only the new token's position ``cache_pos`` of the
        layer's slice is written, the slice is read in place over the valid
        prefix (and ``window``), and the stacks are returned.
      * cross-attention: ``kv_override=(k, v)`` skips rope/cache.

    ``scale`` multiplies the scores (default 1/sqrt(hd)); ``theta <= 0``
    applies no rotary embedding (NoPE).
    """
    from ..hints import constrain

    B, S, d = x.shape
    H, K, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    with jax.named_scope("attn_proj"):
        # head-aligned layout: shard heads over "model" when divisible, else
        # replicate — never let GSPMD split hd (see hints.py docstring)
        q = constrain(dense(p["wq"], x).reshape(B, S, H, hd), "dp", None, "model", None)
        if kv_override is None:
            k = constrain(dense(p["wk"], x).reshape(B, S, K, hd), "dp", None, "model", None)
            v = constrain(dense(p["wv"], x).reshape(B, S, K, hd), "dp", None, "model", None)
        else:
            k, v = kv_override

        if cfg.qk_norm:
            q = head_rmsnorm(p["q_norm"], q, cfg.norm_eps)
            if kv_override is None:
                k = head_rmsnorm(p["k_norm"], k, cfg.norm_eps)

        if kv_override is None and theta > 0:
            cos, sin = rope_tables(positions, hd, theta)
            q = apply_rope(q, cos, sin)
            k = apply_rope(k, cos, sin)

    if kv_override is not None:
        with jax.named_scope("attend"):
            out = sdpa(q, k, v, causal=False, scale=scale)
        new_cache = None
    elif cache is None:
        with jax.named_scope("attend"):
            if use_kernels:
                from ..kernels import ops as kops
                out = kops.flash_attention(q, k, v, causal=causal, window=window,
                                           scale=scale)
            elif S >= BLOCKWISE_THRESHOLD:
                out = blockwise_sdpa(q, k, v, causal=causal, window=window, scale=scale)
            else:
                out = sdpa(q, k, v, causal=causal, window=window, scale=scale)
        new_cache = KVCache(k, v)
    else:
        kv_order = kv_order_for(hd)
        with jax.named_scope("kv_write"):
            # one position of the layer's slice changes; the stacks stay in
            # place through the layer scan
            ck = _write_position(cache.k, k, layer, cache_pos, kv_order)
            cv = _write_position(cache.v, v, layer, cache_pos, kv_order)
        with jax.named_scope("attend"):
            # the layer's slice as (B, S_max, K, hd); the compiler folds the
            # transpose into the attention's reads (re-laying the slice out
            # as (B*K, S_max, hd) matrices first is slower)
            back = tuple(kv_order.index(a) for a in range(4))
            kc = jax.lax.dynamic_index_in_dim(ck, layer, keepdims=False)
            vc = jax.lax.dynamic_index_in_dim(cv, layer, keepdims=False)
            kc, vc = kc.transpose(back), vc.transpose(back)
            if use_kernels:
                from ..kernels import ops as kops
                out = kops.decode_attention(
                    q, kc, vc, kv_len=cache_pos + S, window=window, scale=scale
                )
            else:
                out = sdpa(
                    q, kc, vc,
                    causal=True,
                    window=window,
                    q_offset=cache_pos,
                    kv_len=cache_pos + S,
                    scale=scale,
                )
        new_cache = KVCache(ck, cv)

    with jax.named_scope("attn_proj"):
        y = dense(p["wo"], out.reshape(B, S, H * hd))
    return y, new_cache


def _write_position(stack, new, layer, pos, order):
    """``stack`` (L, ...) in ``order`` with layer ``layer``'s position ``pos``
    set to ``new`` (B, 1, K, hd). The sequence lies on the lanes or the
    sublanes, and the aligned block of LANES or SUBLANES positions that holds
    ``pos`` is rewritten whole: an update one lane or one sublane wide would
    force the stack into another layout."""
    row = new.transpose(order)[None]
    ax = kv_axes(new.shape[-1]).seq         # 4: lanes, 3: sublanes
    S = stack.shape[ax]
    blk = LANES if ax == 4 else SUBLANES
    blk = blk if S % blk == 0 else S
    at = tuple(pos // blk * blk if i == ax else layer if i == 0 else 0 for i in range(5))
    size = row.shape[:ax] + (blk,) + row.shape[ax + 1:]
    old = jax.lax.dynamic_slice(stack, at, size)
    hit = (jnp.arange(blk) == pos % blk).reshape((blk,) + (1,) * (4 - ax))
    return jax.lax.dynamic_update_slice(stack, jnp.where(hit, row, old), at)
