"""Operations and bytes of the served model, from shapes alone.

``needed`` counts what the algorithm requires: causal attention over the
positions a query may see, the valid part of the decode cache. ``executed``
counts what the program's jnp path computes (full score matrices, the whole
cache),
and is checked against ``repro.runtime.costs.jaxpr_costs`` at small sizes.
Roofline shares and MFU use ``needed``, so they stay below 100%.
"""

from __future__ import annotations

from .reference import Dims


def _bytes_of(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[dtype]


def layer_proj_flops(dims: Dims) -> int:
    """Matrix-product operations of one token through one layer's projections
    and MLP."""
    d, H, K, hd = dims.d, dims.H, dims.K, dims.hd
    return 2 * (d * H * hd + 2 * d * K * hd + H * hd * d) + 2 * 3 * d * dims.f


def attn_flops(dims: Dims, B: int, S: int, T: int, executed: bool, q_offset: int = 0) -> int:
    """QK^T and PV for S queries (starting at position ``q_offset``) over T
    keys; ``needed`` counts only the keys each query may see."""
    pairs = S * T if executed else S * q_offset + S * (S + 1) // 2
    return 2 * 2 * B * dims.H * dims.hd * pairs


def lm_head_flops(dims: Dims, rows: int) -> int:
    return 2 * rows * dims.d * dims.Vp


def prefill_flops(dims: Dims, B: int, S: int, executed: bool = False) -> int:
    per_layer = B * S * layer_proj_flops(dims) + attn_flops(dims, B, S, S, executed)
    return dims.L * per_layer + lm_head_flops(dims, B)


def decode_flops(dims: Dims, B: int, pos: int, S_max: int, executed: bool = False) -> int:
    """One decode step whose new token sits at position ``pos``."""
    per_layer = B * layer_proj_flops(dims) \
        + attn_flops(dims, B, 1, S_max if executed else pos + 1, executed, q_offset=pos)
    return dims.L * per_layer + lm_head_flops(dims, B)


def weight_bytes(dims: Dims) -> int:
    """Bytes of the weights a forward pass reads (the embedding table only as
    an unembedding; the rows gathered by the embedding are counted apart)."""
    b = _bytes_of(dims.dtype)
    d, H, K, hd = dims.d, dims.H, dims.K, dims.hd
    per_layer = (d * H * hd + 2 * d * K * hd + H * hd * d + 2 * d + 3 * d * dims.f) * b
    return dims.L * per_layer + dims.Vp * d * b + d * b


def kv_bytes(dims: Dims, B: int, positions: int) -> int:
    return dims.L * B * positions * dims.K * dims.hd * 2 * _bytes_of(dims.dtype)


def prefill_bytes(dims: Dims, B: int, S: int) -> int:
    """Weights read once, the prompt's rows of the embedding, the cache written."""
    return weight_bytes(dims) + B * S * dims.d * _bytes_of(dims.dtype) + kv_bytes(dims, B, S)


def decode_bytes(dims: Dims, B: int, pos: int) -> int:
    """Weights read once, the valid cache read, the new token's K/V written."""
    return weight_bytes(dims) + B * dims.d * _bytes_of(dims.dtype) + kv_bytes(dims, B, pos + 1)
