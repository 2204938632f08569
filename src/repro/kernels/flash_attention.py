"""Flash attention (causal / sliding-window, GQA) as a Pallas TPU kernel.

Tiling: grid = (B*H, S/BQ, T/BK) with the KV axis innermost ("arbitrary"
semantics); online-softmax state (m, l, acc) lives in VMEM scratch. Query
tiles are (BQ, hd) and KV tiles (BK, hd); hd and the tile sizes should be
multiples of 128 on real TPU (the MXU contraction dims), while interpret
mode (CPU validation) accepts any size.

GQA is handled in the index maps: query head h reads kv head h // (H/K).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG = -2.0e38


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            scale: float, causal: bool, window: Optional[int],
            BQ: int, BK: int, nk: int):
    ik = pl.program_id(2)
    iq = pl.program_id(1)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)          # (BQ, hd)
    k = k_ref[0].astype(jnp.float32)          # (BK, hd)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    ) * scale                                  # (BQ, BK)

    rows = iq * BQ + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 0)
    cols = ik * BK + jax.lax.broadcasted_iota(jnp.int32, (BQ, BK), 1)
    ok = jnp.ones((BQ, BK), dtype=bool)
    if causal:
        ok &= cols <= rows
    if window is not None:
        ok &= cols > rows - window
    s = jnp.where(ok, s, _NEG)

    m_prev = m_ref[...]                        # (BQ, 1)
    m_cur = jnp.max(s, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
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


def flash_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    *,
    causal: bool = True,
    window: Optional[int] = None,
    block_q: int = 128,
    block_k: int = 128,
    scale: Optional[float] = None,
    interpret: bool = True,
) -> jnp.ndarray:
    """q: (B,S,H,hd); k/v: (B,T,K,hd) -> (B,S,H,hd). ``scale`` multiplies
    the scores (default 1/sqrt(hd))."""
    B, S, H, hd = q.shape
    T, K = k.shape[1], k.shape[2]
    G = H // K
    BQ = min(block_q, S)
    BK = min(block_k, T)
    if S % BQ or T % BK:
        raise ValueError(f"S={S} % {BQ} or T={T} % {BK} != 0")
    nq, nk = S // BQ, T // BK

    qh = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(B * K, T, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(B * K, T, hd)

    kernel = functools.partial(
        _kernel, scale=hd ** -0.5 if scale is None else scale, causal=causal, window=window,
        BQ=BQ, BK=BK, nk=nk,
    )
    out = pl.pallas_call(
        kernel,
        name="flash_attention",
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, BQ, hd), lambda bh, iq, ik: (bh, iq, 0)),
            pl.BlockSpec((1, BK, hd), lambda bh, iq, ik, G=G, K=K, H=H:
                         ((bh // H) * K + (bh % H) // G, ik, 0)),
            pl.BlockSpec((1, BK, hd), lambda bh, iq, ik, G=G, K=K, H=H:
                         ((bh // H) * K + (bh % H) // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, BQ, hd), lambda bh, iq, ik: (bh, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((BQ, 1), jnp.float32),
            pltpu.VMEM((BQ, 1), jnp.float32),
            pltpu.VMEM((BQ, hd), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
