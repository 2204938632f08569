"""jit'd public wrappers for the Pallas kernels.

On a TPU the kernels compile natively. On the CPU backend (the tests) they
run in interpret mode: Python evaluation of the kernel body, checked against
``ref.py``. No other switch exists, so a chip run always runs native kernels.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from . import decode_attention as _dec
from . import flash_attention as _fa
from . import rmsnorm as _rn
from . import ssd_scan as _ssd


def _interpret() -> bool:
    return jax.default_backend() == "cpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q", "block_k",
                                             "scale"))
def flash_attention(q, k, v, *, causal: bool = True, window: Optional[int] = None,
                    block_q: int = 128, block_k: int = 128, scale: Optional[float] = None):
    return _fa.flash_attention(
        q, k, v, causal=causal, window=window,
        block_q=block_q, block_k=block_k, scale=scale, interpret=_interpret(),
    )


@functools.partial(jax.jit, static_argnames=("window", "block_k", "scale"))
def decode_attention(q, k, v, *, kv_len, window: Optional[int] = None,
                     block_k: int = _dec.BLOCK_K, scale: Optional[float] = None):
    return _dec.decode_attention(
        q, k, v, kv_len=kv_len, window=window, block_k=block_k, scale=scale,
        interpret=_interpret(),
    )


@jax.jit
def ssd_intra_chunk(la, C, B_in, x):
    return _ssd.ssd_intra_chunk(la, C, B_in, x, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("eps", "block_rows"))
def rmsnorm(x, scale, *, eps: float = 1e-5, block_rows: int = 256):
    return _rn.rmsnorm(x, scale, eps=eps, block_rows=block_rows,
                       interpret=_interpret())
