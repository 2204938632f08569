"""Mamba2 (SSD) mixer — chunked parallel form for train/prefill, O(1)-state
recurrent form for decode (arXiv:2405.21060, adapted to TPU: chunk size is
MXU-aligned, intra-chunk term is a (Q x Q) matmul, inter-chunk term is a
``lax.scan`` over chunk states).

Recurrence (heads H, head dim P, state N, chunk Q):
    h_t = a_t * h_{t-1} + dt_t * (B_t ⊗ x_t)        h: (H, P, N)
    y_t = C_t · h_t + D * x_t
with a_t = exp(dt_t * A), A = -exp(A_log) < 0.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from .layers import dense, dense_init, rmsnorm


class MambaCache(NamedTuple):
    conv: jnp.ndarray    # (B, W-1, conv_channels) rolling conv input window
    h: jnp.ndarray       # (B, H, P, N) SSM state


def mamba_init(rng, cfg: ModelConfig, *, dtype=jnp.float32):
    d = cfg.d_model
    di = cfg.d_inner_ssm
    N = cfg.ssm_state
    H = cfg.n_ssm_heads
    W = cfg.ssm_conv_width
    conv_ch = di + 2 * N
    r0, r1, r2, r3 = jax.random.split(rng, 4)
    return {
        "in_proj": dense_init(r0, d, 2 * di + 2 * N, dtype=dtype),
        "dt_proj": dense_init(r2, d, H, dtype=dtype),
        "conv_w": (jax.random.normal(r1, (W, conv_ch)) * (W ** -0.5)).astype(dtype),
        "conv_b": jnp.zeros((conv_ch,), dtype=dtype),
        "dt_bias": jnp.zeros((H,), dtype=jnp.float32),
        "A_log": jnp.zeros((H,), dtype=jnp.float32),
        "D": jnp.ones((H,), dtype=jnp.float32),
        "gnorm": {"scale": jnp.ones((di,), dtype=dtype)},
        "out_proj": dense_init(r3, di, d, dtype=dtype),
    }


def _causal_conv(x, w, b):
    """Depthwise causal conv. x: (B, S, Ch); w: (W, Ch)."""
    W = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (W - 1, 0), (0, 0)))
    out = jax.lax.conv_general_dilated(
        xp,
        w[:, None, :],
        window_strides=(1,),
        padding="VALID",
        dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=x.shape[-1],
    )
    return out + b


def ssd_chunked(x, dt, A, B_in, C_in, Q: int, h0=None, *, use_kernel: bool = False):
    """Chunked SSD scan.

    x: (B, S, H, P); dt: (B, S, H) (post-softplus); A: (H,) negative;
    B_in/C_in: (B, S, N) (single group, shared across heads).
    Returns y: (B, S, H, P) and final state (B, H, P, N), float32.

    A ``lax.scan`` visits the chunks in order and carries the state, so the
    intra-chunk work (each head's decay-masked (Q, Q) products) is held for
    one chunk at a time and does not grow with the sequence. With
    ``use_kernel`` that work runs in the Pallas kernel
    (``kernels/ssd_scan.py``).
    """
    Bsz, S, H, P = x.shape
    N = B_in.shape[-1]
    S_orig = S
    if S % Q:
        # pad tail with identity steps: dt=0 -> a=1, xbar=0 -> state unchanged
        pad = Q - S % Q
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        B_in = jnp.pad(B_in, ((0, 0), (0, pad), (0, 0)))
        C_in = jnp.pad(C_in, ((0, 0), (0, pad), (0, 0)))
        S = S + pad
    f32 = jnp.float32
    tri = jnp.tril(jnp.ones((Q, Q), dtype=bool))
    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), dtype=f32)

    def chunk(carry, c):
        h, y = carry                                    # state entering the chunk
        def sl(a):
            return jax.lax.dynamic_slice_in_dim(a, c * Q, Q, axis=1)
        dtc = sl(dt).astype(f32)                        # (B,Q,H)
        xbar = dtc[..., None] * sl(x).astype(f32)       # (B,Q,H,P)
        Bc, Cc = sl(B_in).astype(f32), sl(C_in).astype(f32)   # (B,Q,N)
        la = dtc * A                                    # log a_t
        L = jnp.cumsum(la, axis=1)                      # (B,Q,H)
        if use_kernel:
            from ..kernels import ops as kops
            y_intra, st, _ = kops.ssd_intra_chunk(la[:, None], Cc[:, None], Bc[:, None],
                                                  xbar[:, None])
            y_intra, st = y_intra[:, 0], st[:, 0]
        else:
            # y[t] = sum_{s<=t} exp(L_t - L_s) (C_t.B_s) xbar_s
            CB = jnp.einsum("bqn,bsn->bqs", Cc, Bc)     # (B,Q,Q)
            Lh = L.transpose(0, 2, 1)                   # (B,H,Q)
            seg = Lh[:, :, :, None] - Lh[:, :, None, :]  # (B,H,Q,Q)
            M = jnp.exp(jnp.where(tri, seg, -jnp.inf)) * CB[:, None]
            y_intra = jnp.einsum("bhqs,bshp->bqhp", M, xbar)
            # the chunk's state: sum_s exp(L_end - L_s) xbar_s ⊗ B_s
            w_end = jnp.exp(L[:, -1:, :] - L)           # (B,Q,H)
            st = jnp.einsum("bqh,bqhp,bqn->bhpn", w_end, xbar, Bc)
        # y_inter[t] = exp(L_t) * C_t · h
        y_inter = jnp.exp(L)[..., None] * jnp.einsum("bqn,bhpn->bqhp", Cc, h)
        h = jnp.exp(L[:, -1])[:, :, None, None] * h + st
        y = jax.lax.dynamic_update_slice_in_dim(
            y, (y_intra + y_inter).astype(y.dtype), c * Q, axis=1)
        return (h, y), None

    (hT, y), _ = jax.lax.scan(chunk, (h0, jnp.zeros(x.shape, x.dtype)), jnp.arange(S // Q))
    return y[:, :S_orig], hT


def mamba_forward(p, x, cfg: ModelConfig, *, cache: MambaCache | None = None,
                  use_kernels: bool = False):
    """One mamba2 mixer. x: (B, S, d). Returns (out, MambaCache): the conv
    window and SSM state after the last position. With ``cache`` (decode) S
    must be 1 and the state steps once; without, the chunked scan runs from a
    zero state.

    Named scopes: ``ssm_proj`` (in/out projections, gate and gated norm),
    ``ssm_conv`` (the causal conv), ``ssm_scan`` (the chunked scan) and
    ``ssm_state`` (the one-step state update)."""
    from ..hints import constrain

    Bsz, S, d = x.shape
    di = cfg.d_inner_ssm
    N = cfg.ssm_state
    H = cfg.n_ssm_heads
    P = cfg.ssm_head_dim
    W = cfg.ssm_conv_width

    with jax.named_scope("ssm_proj"):
        # z and (x, B, C) from one matrix, dt from its own: a single matrix of
        # width 2 di + 2 N + H is not a multiple of 128 wide, and the TPU
        # lays it out transposed and copies it back at every call
        z, xBC = jnp.split(dense(p["in_proj"], x), [di], axis=-1)
        dt_raw = dense(p["dt_proj"], x)

    with jax.named_scope("ssm_conv"):
        if cache is None:
            window = jnp.pad(xBC[:, -(W - 1):], ((0, 0), (max(0, W - 1 - S), 0), (0, 0)))
            xBC = jax.nn.silu(_causal_conv(xBC, p["conv_w"], p["conv_b"]))
        else:
            window = jnp.concatenate([cache.conv, xBC], axis=1)     # (B, W, Ch)
            conv_out = jnp.einsum("bwc,wc->bc", window, p["conv_w"]) + p["conv_b"]
            xBC = jax.nn.silu(conv_out)[:, None, :]
            window = window[:, 1:, :]

    xs, B_in, C_in = jnp.split(xBC, [di, di + N], axis=-1)
    xs = constrain(xs.reshape(Bsz, S, H, P), "dp", None, "model", None)
    B_in = constrain(B_in, "dp", None, None)
    C_in = constrain(C_in, "dp", None, None)

    if cache is None:
        with jax.named_scope("ssm_scan"):
            dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
            A = -jnp.exp(p["A_log"])
            y, h = ssd_chunked(xs, dt, A, B_in, C_in, cfg.ssm_chunk,
                               use_kernel=use_kernels)
            y = y + p["D"].astype(y.dtype)[None, None, :, None] * xs
    else:
        with jax.named_scope("ssm_state"):
            dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"])
            A = -jnp.exp(p["A_log"])
            a = jnp.exp(dt * A)                                     # (B,1,H)
            xbar = (dt[..., None] * xs).astype(jnp.float32)         # (B,1,H,P)
            dh = jnp.einsum("bhp,bn->bhpn", xbar[:, 0], B_in[:, 0].astype(jnp.float32))
            h = a[:, 0, :, None, None] * cache.h + dh
            y = jnp.einsum("bn,bhpn->bhp", C_in[:, 0].astype(jnp.float32), h)
            y = y[:, None].astype(x.dtype)
            y = y + p["D"].astype(y.dtype)[None, None, :, None] * xs

    with jax.named_scope("ssm_proj"):
        y = y.reshape(Bsz, S, di)
        y = rmsnorm(p["gnorm"], y * jax.nn.silu(z), cfg.norm_eps)
        out = dense(p["out_proj"], y)
    return out, MambaCache(conv=window, h=h)


def empty_mamba_cache(cfg: ModelConfig, B: int, dtype) -> MambaCache:
    di, N, H, P, W = (
        cfg.d_inner_ssm, cfg.ssm_state, cfg.n_ssm_heads, cfg.ssm_head_dim,
        cfg.ssm_conv_width,
    )
    return MambaCache(
        conv=jnp.zeros((B, W - 1, di + 2 * N), dtype=dtype),
        h=jnp.zeros((B, H, P, N), dtype=jnp.float32),
    )
