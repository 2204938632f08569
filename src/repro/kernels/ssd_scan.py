"""Mamba2 SSD intra-chunk kernel (Pallas TPU).

The chunked SSD algorithm splits into (a) per-chunk quadratic work --
build the decay-masked (Q x Q) score matrix, apply it to the inputs, and
reduce the chunk's contribution to the running state -- and (b) a cheap
inter-chunk linear scan. (a) is the MXU-heavy part and lives here; (b)
stays a ``lax.scan`` on the host graph (see ``models/mamba2.ssd_chunked``).

Grid: (B * nc, H). Per step the kernel holds the chunk's C/B (Q, N),
x (Q, P) and the log-decays (Q,) in VMEM, and emits y_intra (Q, P) and the
chunk state contribution (P, N). Every block's last two dims are either the
array's own or (1, Q)/(Q, 1), so the layout is legal for the TPU's (8, 128)
tiling. The log-decays come in twice, as a row and as a column, so both
cumulative sums are masked reductions: no transpose and no 1-D vectors in
the kernel. The chunk's total decay is a plain sum, taken outside.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(la_row_ref, la_col_ref, c_ref, b_ref, x_ref, y_ref, st_ref, *, Q: int):
    la_row = la_row_ref[0, 0].astype(jnp.float32)   # (1, Q)
    la_col = la_col_ref[0, 0].astype(jnp.float32)   # (Q, 1)
    C = c_ref[0].astype(jnp.float32)                 # (Q, N)
    Bm = b_ref[0].astype(jnp.float32)                # (Q, N)
    x = x_ref[0, 0].astype(jnp.float32)              # (Q, P)

    rows = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (Q, Q), 1)
    causal = cols <= rows
    # L = cumsum(la), as a column (L_t) and as a row (L_s)
    L_col = jnp.sum(jnp.where(causal, la_row, 0.0), axis=1, keepdims=True)
    L_row = jnp.sum(jnp.where(rows <= cols, la_col, 0.0),
                    axis=0, keepdims=True)
    # intra-chunk: M[t,s] = exp(L_t - L_s) * (C_t . B_s)  for s <= t
    CB = jax.lax.dot_general(
        C, Bm, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
    )                                                # (Q, Q)
    M = jnp.where(causal, jnp.exp(L_col - L_row) * CB, 0.0)
    y_ref[0, 0] = jax.lax.dot_general(
        M, x, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(y_ref.dtype)

    # chunk state contribution: sum_s exp(L_end - L_s) x_s ⊗ B_s -> (P, N)
    L_end = jnp.sum(la_row, axis=1, keepdims=True)   # (1, 1)
    xw = x * jnp.exp(L_end - L_col)                  # (Q, P)
    st_ref[0, 0] = jax.lax.dot_general(
        xw, Bm, (((0,), (0,)), ((), ())), preferred_element_type=jnp.float32
    ).astype(st_ref.dtype)


def ssd_intra_chunk(la, C, B_in, x, *, interpret: bool = True):
    """la: (B, nc, Q, H) log-decay; C/B_in: (B, nc, Q, N); x: (B, nc, Q, H, P).

    Returns (y_intra (B,nc,Q,H,P) f32, states (B,nc,H,P,N) f32,
    tot (B,nc,H) f32 total log-decay per chunk).
    """
    Bs, nc, Q, H = la.shape
    N = C.shape[-1]
    P = x.shape[-1]
    n = Bs * nc

    la_h = la.transpose(0, 1, 3, 2).reshape(n, H, Q)
    la_row = la_h.reshape(n, H, 1, Q)
    la_col = la_h.reshape(n, H, Q, 1)
    c_r = C.reshape(n, Q, N)
    b_r = B_in.reshape(n, Q, N)
    x_r = x.transpose(0, 1, 3, 2, 4).reshape(n, H, Q, P)

    kernel = functools.partial(_kernel, Q=Q)
    y, st = pl.pallas_call(
        kernel,
        name="ssd_scan",
        grid=(n, H),
        in_specs=[
            pl.BlockSpec((1, 1, 1, Q), lambda i, h: (i, h, 0, 0)),
            pl.BlockSpec((1, 1, Q, 1), lambda i, h: (i, h, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda i, h: (i, 0, 0)),
            pl.BlockSpec((1, Q, N), lambda i, h: (i, 0, 0)),
            pl.BlockSpec((1, 1, Q, P), lambda i, h: (i, h, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, 1, Q, P), lambda i, h: (i, h, 0, 0)),
            pl.BlockSpec((1, 1, P, N), lambda i, h: (i, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((n, H, Q, P), jnp.float32),
            jax.ShapeDtypeStruct((n, H, P, N), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
    )(la_row, la_col, c_r, b_r, x_r)

    y = y.reshape(Bs, nc, H, Q, P).transpose(0, 1, 3, 2, 4)
    st = st.reshape(Bs, nc, H, P, N)
    tot = jnp.sum(la.astype(jnp.float32), axis=2)
    return y, st, tot
