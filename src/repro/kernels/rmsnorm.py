"""Fused RMSNorm Pallas kernel: one pass over rows, fp32 statistics in-tile.

Grid: rows / BR. Tile (BR, d) stays in VMEM; d up to ~8k rows fit easily
(BR * d * 4B << 16 MiB VMEM for BR=256, d=8192 -> 8 MiB).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(x_ref, s_ref, o_ref, *, eps: float):
    x = x_ref[...].astype(jnp.float32)
    scale = s_ref[...].astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    o_ref[...] = (x * jax.lax.rsqrt(var + eps) * scale).astype(o_ref.dtype)


def rmsnorm(x: jnp.ndarray, scale: jnp.ndarray, *, eps: float = 1e-5,
            block_rows: int = 256, interpret: bool = True) -> jnp.ndarray:
    """x: (..., d); scale: (d,)."""
    orig_shape = x.shape
    d = x.shape[-1]
    rows = int(x.size // d)
    xr = x.reshape(rows, d)
    BR = min(block_rows, rows)
    if rows % BR:
        BR = 1
    kernel = functools.partial(_kernel, eps=eps)
    out = pl.pallas_call(
        kernel,
        name="rmsnorm",
        grid=(rows // BR,),
        in_specs=[
            pl.BlockSpec((BR, d), lambda i: (i, 0)),
            pl.BlockSpec((d,), lambda i: (0,)),
        ],
        out_specs=pl.BlockSpec((BR, d), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((rows, d), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
    )(xr, scale)
    return out.reshape(orig_shape)
