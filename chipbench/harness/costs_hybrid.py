"""Operations and bytes of the served Mamba2 hybrid, from shapes alone.

As in ``costs.py``: ``needed`` counts what the algorithm requires (causal
attention over the positions a query may see, the SSD's intra-chunk products
over the causal pairs of each chunk, the valid part of the KV cache);
``executed`` counts what the program's jnp path computes (whole (Q, Q)
blocks, whole score matrices, the whole cache, the sequence padded to whole
chunks, the depthwise conv as ``jaxpr_costs`` counts it) and is checked
against ``repro.runtime.costs.jaxpr_costs`` at small sizes. Shares and MFU
use ``needed``, so they stay below 100%.
"""

from __future__ import annotations

from .reference_hybrid import HDims

F32_BYTES = 4


def _bytes_of(dtype: str) -> int:
    return {"bfloat16": 2, "float16": 2, "float32": 4}[dtype]


def _pairs(S: int, Q: int, executed: bool) -> int:
    """(query, key) pairs inside the chunks of a sequence of S."""
    n, r = divmod(S, Q)
    if executed:
        return (n + (r > 0)) * Q * Q
    return n * Q * (Q + 1) // 2 + r * (r + 1) // 2


def ssd_flops(dims: HDims, B: int, S: int, executed: bool = False) -> int:
    """The chunked SSD of one layer over S positions: C.B products and their
    application within each chunk, each chunk's state, and the state's read
    at every position."""
    N, Hs, P, Q = dims.N, dims.Hs, dims.P, dims.Q
    rows = -(-S // Q) * Q if executed else S
    intra = 2 * B * _pairs(S, Q, executed) * (N + Hs * P)
    states = 2 * B * rows * Hs * P * N
    inter = 2 * B * rows * N * Hs * P
    if executed:                            # B weighted by the decay, per head
        states += 2 * B * rows * Hs * N
    return intra + states + inter


def ssd_bytes(dims: HDims, B: int, S: int) -> int:
    """Bytes the SSD of one layer must move: its inputs (x, B, C, dt) and its
    output y read and written once, and the final state written."""
    b = _bytes_of(dims.dtype)
    return B * S * (2 * dims.di + 2 * dims.N + dims.Hs) * b + B * dims.Hs * dims.P * dims.N * F32_BYTES


def mamba_proj_flops(dims: HDims, rows: int) -> int:
    """in_proj, dt_proj and out_proj over ``rows`` positions."""
    d, di, N, Hs = dims.d, dims.di, dims.N, dims.Hs
    return 2 * rows * d * (2 * di + 2 * N + Hs) + 2 * rows * di * d


def conv_flops(dims: HDims, rows: int, executed: bool) -> int:
    """The causal depthwise conv; ``jaxpr_costs`` counts a grouped conv's
    kernel once for all its channels."""
    return 2 * rows * dims.W * (1 if executed else dims.di + 2 * dims.N)


def attn_proj_flops(dims: HDims, rows: int) -> int:
    d, H, K, hd = dims.d, dims.H, dims.K, dims.hd
    return 2 * rows * d * (H + 2 * K) * hd + 2 * rows * H * hd * d


def mlp_flops(dims: HDims, rows: int) -> int:
    return 2 * rows * 3 * dims.d * dims.f


def lm_head_flops(dims: HDims, rows: int) -> int:
    return 2 * rows * dims.d * dims.Vp


def prefill_flops(dims: HDims, B: int, S: int, executed: bool = False) -> int:
    rows = B * S
    pairs = S * S if executed else S * (S + 1) // 2
    mamba = mamba_proj_flops(dims, rows) + conv_flops(dims, rows, executed) \
        + ssd_flops(dims, B, S, executed)
    attn = attn_proj_flops(dims, rows) + 2 * 2 * B * dims.H * dims.hd * pairs
    return dims.n_mamba * mamba + dims.n_attn * attn + dims.L * mlp_flops(dims, rows) \
        + lm_head_flops(dims, B)


def state_step_flops(dims: HDims, B: int) -> int:
    """One decode step of one layer's state: the update and its read."""
    return 2 * 2 * B * dims.Hs * dims.P * dims.N


def decode_flops(dims: HDims, B: int, pos: int, S_max: int, executed: bool = False) -> int:
    """One decode step whose new token sits at position ``pos``."""
    keys = S_max if executed else pos + 1
    conv = 2 * B * dims.W * (dims.di + 2 * dims.N)
    mamba = mamba_proj_flops(dims, B) + conv + state_step_flops(dims, B)
    attn = attn_proj_flops(dims, B) + 2 * 2 * B * dims.H * dims.hd * keys
    return dims.n_mamba * mamba + dims.n_attn * attn + dims.L * mlp_flops(dims, B) \
        + lm_head_flops(dims, B)


def weight_bytes(dims: HDims) -> int:
    """Bytes of the weights a forward pass reads (the tied embedding table
    once, as the unembedding)."""
    b = _bytes_of(dims.dtype)
    d, di, N, Hs, W = dims.d, dims.di, dims.N, dims.Hs, dims.W
    ch = di + 2 * N
    mamba = (d * (2 * di + 2 * N + Hs) + (W + 1) * ch + di + di * d) * b + 3 * Hs * F32_BYTES
    attn = (d * (dims.H + 2 * dims.K) * dims.hd + dims.H * dims.hd * d) * b
    common = (3 * d * dims.f + 2 * d) * b
    return dims.n_mamba * mamba + dims.n_attn * attn + dims.L * common \
        + dims.Vp * d * b + d * b


def state_bytes(dims: HDims, B: int) -> int:
    """One layer's float32 SSM state for B sequences."""
    return B * dims.Hs * dims.P * dims.N * F32_BYTES


def state_step_bytes(dims: HDims, B: int) -> int:
    """Bytes of every layer's state read and written once: the least the
    decode step's state update moves."""
    return dims.n_mamba * 2 * state_bytes(dims, B)


def kv_bytes(dims: HDims, B: int, positions: int) -> int:
    return dims.n_attn * B * positions * dims.K * dims.hd * 2 * _bytes_of(dims.dtype)


def decode_bytes(dims: HDims, B: int, pos: int) -> int:
    """Weights read once, the states and conv windows read and written, the
    valid KV read and the new token's written."""
    b = _bytes_of(dims.dtype)
    conv = dims.n_mamba * 2 * B * (dims.W - 1) * (dims.di + 2 * dims.N) * b
    return weight_bytes(dims) + B * dims.d * b + state_step_bytes(dims, B) + conv \
        + kv_bytes(dims, B, pos + 1)
