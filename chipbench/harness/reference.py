"""The plain reference: a dense decoder LM in float32 ``jax.numpy``.

It imports nothing of the program and takes nothing the program made: its
weights are drawn again from the run's seed (``weights.py``), and its sizes
are the configuration file's. Every matrix product runs at ``highest``
precision, so float32 on the TPU is float32.

It follows the configuration as the program runs it, which the file states:
full rotary embeddings in the half-split layout, RMSNorm, grouped-query
causal attention, SwiGLU. The logits run over the padded vocabulary, whose
extra rows the weights set to zero.

``quant="fp8"`` is the control: the same model with every matrix product's
operands rounded to float8 (e4m3, one scale per row of activations and per
output column of weights), the step below the bfloat16 the configuration
states.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from . import weights as W

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Dims:
    d: int
    H: int
    K: int
    hd: int
    f: int
    L: int
    V: int
    Vp: int
    theta: float
    eps: float
    tie: bool
    dtype: str = "bfloat16"

    @classmethod
    def of(cls, c: dict) -> "Dims":
        V = c["vocab_size"]
        return cls(
            d=c["hidden_size"], H=c["num_attention_heads"], K=c["num_key_value_heads"],
            hd=c["head_dim"], f=c["intermediate_size"], L=c["num_hidden_layers"],
            V=V, Vp=c["padded_vocab_size"], theta=float(c["rope_theta"]),
            eps=float(c["rms_norm_eps"]), tie=bool(c["tie_word_embeddings"]),
            dtype=c["torch_dtype"])

    def layer_shapes(self) -> dict:
        d, H, K, hd, f = self.d, self.H, self.K, self.hd, self.f
        return {"attn/wq/w": (d, H * hd), "attn/wk/w": (d, K * hd),
                "attn/wv/w": (d, K * hd), "attn/wo/w": (H * hd, d),
                "ln1/scale": (d,), "ln2/scale": (d,),
                "mlp/gate/w": (d, f), "mlp/up/w": (d, f), "mlp/down/w": (f, d)}


# -- weights, drawn again from the seed -----------------------------------------
def layer_weights(key, dims: Dims, layer):
    dt = jnp.dtype(dims.dtype)
    return {name: W.draw_layer(key, f"layers/{name}", shape, layer, dims.V, dt).astype(F32)
            for name, shape in dims.layer_shapes().items()}


def top_weights(key, dims: Dims):
    out = {"embed": W.draw(key, "embed/w", (dims.Vp, dims.d), dims.V,
                           jnp.dtype(dims.dtype)).astype(F32),
           "final_norm": jnp.ones((dims.d,), F32)}
    if not dims.tie:
        out["unembed"] = W.draw(key, "unembed/w", (dims.Vp, dims.d), dims.V,
                                jnp.dtype(dims.dtype)).astype(F32)
    return out


# -- numerics -----------------------------------------------------------------
def _fp8(x, axis):
    """Round to float8 e4m3 with one scale along ``axis`` (amax -> 448)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(F32) * s


def matmul(x, w, quant=None):
    """x (..., n) @ w (n, m); the control rounds both operands to fp8."""
    if quant == "fp8":
        x, w = _fp8(x, -1), _fp8(w, -2)
    return jnp.matmul(x, w, precision="highest")


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (B, S, h, hd): rotate (first half, second half) pairs."""
    hd = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=F32) / hd)
    ang = pos.astype(F32)[:, None] * inv[None, :]
    c, s = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def attention(x, w, dims: Dims, quant=None, q_chunk=512):
    """Causal GQA self-attention over x (B, S, d); exact softmax over all keys,
    one block of query rows at a time so that long sequences fit."""
    B, S, _ = x.shape
    H, K, hd = dims.H, dims.K, dims.hd
    pos = jnp.arange(S)
    q = rope(matmul(x, w["attn/wq/w"], quant).reshape(B, S, H, hd), pos, dims.theta)
    k = rope(matmul(x, w["attn/wk/w"], quant).reshape(B, S, K, hd), pos, dims.theta)
    v = matmul(x, w["attn/wv/w"], quant).reshape(B, S, K, hd)
    k = jnp.repeat(k, H // K, axis=2)
    v = jnp.repeat(v, H // K, axis=2)
    qc = min(q_chunk, S)
    nq = S // qc
    if nq * qc != S:
        raise ValueError(f"sequence {S} is not a multiple of the query block {qc}")

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qc, qc, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision="highest") * hd ** -0.5
        rows = i * qc + jnp.arange(qc)[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= rows, s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p, v, precision="highest")

    o = jax.lax.map(block, jnp.arange(nq))            # (nq, B, qc, H, hd)
    o = o.transpose(1, 0, 2, 3, 4).reshape(B, S, H * hd)
    return matmul(o, w["attn/wo/w"], quant)


def dense_mlp(x, w, quant=None):
    g = matmul(x, w["mlp/gate/w"], quant)
    u = matmul(x, w["mlp/up/w"], quant)
    return matmul(jax.nn.silu(g) * u, w["mlp/down/w"], quant)


def layer(x, w, dims: Dims, quant=None):
    x = x + attention(rmsnorm(x, w["ln1/scale"], dims.eps), w, dims, quant)
    return x + dense_mlp(rmsnorm(x, w["ln2/scale"], dims.eps), w, quant)


def unembed_matrix(top, dims: Dims):
    return top["embed"] if dims.tie else top["unembed"]


# -- serving: logit gaps of served tokens ---------------------------------------
def served_gaps(key, dims: Dims, tokens, targets, valid, start: int, *, quant=None):
    """For each sequence in ``tokens`` (N, T): the reference's logits at
    positions ``start .. start + L - 1``, L being the width of ``targets``
    (N, L); returns (N, L) gaps by which each target lies below the best
    logit there (0 where ``valid`` is false). With ``quant`` set, the gap is
    that of the token the quantised model puts first, read in float32.

    Runs layer by layer, drawing each layer's weights in the call, so only
    one layer of float32 weights is on the device at a time. Everything that
    changes with the seed is an argument, so each program compiles once."""
    top = jax.jit(top_weights, static_argnums=1)(key, dims)
    x = _embed(top["embed"], tokens)
    xq = x
    for l in range(dims.L):
        x = _ref_layer(x, key, jnp.int32(l), dims, None)
        if quant:
            xq = _ref_layer(xq, key, jnp.int32(l), dims, quant)
    return _gaps(x, xq, top, targets, valid, start, dims, quant)


@jax.jit
def _embed(table, tokens):
    return table[tokens]


@functools.partial(jax.jit, static_argnums=(3, 4))
def _ref_layer(x, key, l, dims: Dims, quant):
    return layer(x, layer_weights(key, dims, l), dims, quant)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _gaps(x, xq, top, targets, valid, start, dims: Dims, quant):
    L = targets.shape[1]
    um = unembed_matrix(top, dims)
    h = rmsnorm(x[:, start:start + L], top["final_norm"], dims.eps)
    logits = jnp.einsum("ntd,vd->ntv", h, um, precision="highest")
    best = logits.max(-1)
    if quant:
        hq = rmsnorm(xq[:, start:start + L], top["final_norm"], dims.eps)
        pick = jnp.argmax(matmul(hq, um.T, quant), -1)
    else:
        pick = targets
    got = jnp.take_along_axis(logits, pick[..., None], -1)[..., 0]
    return jnp.where(valid, best - got, 0.0)
