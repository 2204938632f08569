"""Single-token decode attention (flash-decode style) as a Pallas TPU kernel.

One query row per (batch, head); the KV cache is streamed in BK-sized tiles
with online softmax; only the valid prefix (``kv_len``) contributes. The
``kv_len`` scalar rides in SMEM (runtime value, no retrace per step).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -2.0e38
BLOCK_K = 512       # default KV tile; a cache longer than this is a multiple of it


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, window: Optional[int], BK: int, nk: int):
    ik = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kv_len = len_ref[0]
    q = q_ref[0].astype(jnp.float32)           # (1, hd)
    k = k_ref[0].astype(jnp.float32)           # (BK, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                   # (1, BK)
    cols = ik * BK + jax.lax.broadcasted_iota(jnp.int32, (1, BK), 1)
    ok = cols < kv_len
    if window is not None:
        ok &= cols > kv_len - 1 - window
    s = jnp.where(ok, s, _NEG)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
    p = jnp.exp(s - m_new)
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = alpha * l_ref[...] + p.sum(axis=-1, keepdims=True)
    acc_ref[...] = alpha * acc_ref[...] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    )
    m_ref[...] = m_new

    @pl.when(ik == nk - 1)
    def _done():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


def decode_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    kv_len,
    window: Optional[int] = None,
    block_k: int = BLOCK_K,
    scale: Optional[float] = None,
    interpret: bool = True,
) -> jnp.ndarray:
    """q: (B,1,H,hd); k/v: (B,T,K,hd); kv_len: scalar int (# valid entries,
    including the token just written); ``scale`` multiplies the scores
    (default 1/sqrt(hd)). Returns (B,1,H,hd)."""
    B, S, H, hd = q.shape
    assert S == 1, "decode kernel is single-token"
    T, K = k.shape[1], k.shape[2]
    G = H // K
    BK = min(block_k, T)
    if T % BK:
        raise ValueError(f"T={T} % {BK} != 0")
    nk = T // BK

    qh = q.transpose(0, 2, 1, 3).reshape(B * H, 1, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(B * K, T, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(B * K, T, hd)
    len_arr = jnp.asarray(kv_len, jnp.int32).reshape(1)

    kernel = functools.partial(
        _kernel, scale=hd ** -0.5 if scale is None else scale, window=window, BK=BK, nk=nk
    )
    out = pl.pallas_call(
        kernel,
        name="decode_attention",
        grid=(B * H, nk),
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, hd), lambda bh, ik: (bh, 0, 0)),
            pl.BlockSpec((1, BK, hd), lambda bh, ik, G=G, K=K, H=H:
                         ((bh // H) * K + (bh % H) // G, ik, 0)),
            pl.BlockSpec((1, BK, hd), lambda bh, ik, G=G, K=K, H=H:
                         ((bh // H) * K + (bh % H) // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, hd), lambda bh, ik: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, 1, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, 1), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(len_arr, qh, kh, vh)
    return out.reshape(B, H, 1, hd).transpose(0, 2, 1, 3)
