"""The plain reference of a Granite-4.0-H-style hybrid, in float32
``jax.numpy``: layers of Mamba2 or GQA attention, each followed by a SwiGLU
MLP, in the order the configuration file's ``layer_types`` gives.

It imports nothing of the program and takes nothing the program made: its
weights are drawn again from the run's seed (``weights_hybrid.py``), layer by
layer, and its sizes are the configuration file's. Every matrix product runs
at ``highest`` precision. Per layer ``x += r * mixer(rmsnorm(x))`` then
``x += r * swiglu(rmsnorm(x))`` (``r``: ``residual_multiplier``). The mixer:

* Mamba2, one group: ``in_proj`` to (z, x, B, C), ``dt_proj`` to dt; a causal
  depthwise conv with bias over (x, B, C), then SiLU; ``dt = softplus(dt +
  dt_bias)``, ``A = -exp(A_log)``; the recurrence ``h_t = exp(dt_t A) h_{t-1}
  + dt_t (x_t ⊗ B_t)``, ``y_t = C_t · h_t + D x_t``, one position at a time
  from a zero state; ``rmsnorm(y * silu(z))``; ``out_proj``;
* or causal GQA over every earlier position, scores times
  ``attention_multiplier``, no position embedding (``nope``).

Embeddings are multiplied by ``embedding_multiplier``; the logits come from
the tied embedding after a final RMSNorm, divided by ``logits_scaling``, over
the padded vocabulary, whose extra rows the weights set to zero.

``quant="fp8"`` is the control: every matrix product's operands rounded to
float8 (``reference.matmul``), the step below the configuration's bfloat16;
the recurrence itself stays in float32.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp

from . import weights_hybrid as WH
from .reference import _embed, dense_mlp, matmul, rmsnorm

F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class HDims:
    d: int
    H: int
    K: int
    hd: int
    f: int
    L: int
    V: int
    Vp: int
    eps: float
    layer_types: tuple
    di: int               # Mamba inner width
    N: int                # state size
    Hs: int               # Mamba heads
    P: int                # Mamba head size
    W: int                # conv width
    Q: int                # the program's chunk (for the operation counts)
    emb_mult: float
    res_mult: float
    attn_mult: float
    logits_scaling: float
    dtype: str = "bfloat16"

    @classmethod
    def of(cls, c: dict) -> "HDims":
        if c.get("position_embedding_type") != "nope" or not c["tie_word_embeddings"]:
            raise ValueError("the hybrid reference has NoPE attention and tied embeddings")
        if c["mamba_n_groups"] != 1:
            raise ValueError("the hybrid reference has one group of B and C")
        return cls(
            d=c["hidden_size"], H=c["num_attention_heads"], K=c["num_key_value_heads"],
            hd=c["head_dim"], f=c["intermediate_size"], L=c["num_hidden_layers"],
            V=c["vocab_size"], Vp=c["padded_vocab_size"], eps=float(c["rms_norm_eps"]),
            layer_types=tuple(c["layer_types"]),
            di=c["mamba_expand"] * c["hidden_size"], N=c["mamba_d_state"],
            Hs=c["mamba_n_heads"], P=c["mamba_d_head"], W=c["mamba_d_conv"],
            Q=c["mamba_chunk_size"],
            emb_mult=float(c["embedding_multiplier"]),
            res_mult=float(c["residual_multiplier"]),
            attn_mult=float(c["attention_multiplier"]),
            logits_scaling=float(c["logits_scaling"]), dtype=c["torch_dtype"])

    @property
    def n_attn(self) -> int:
        return self.layer_types.count("attention")

    @property
    def n_mamba(self) -> int:
        return self.L - self.n_attn

    def mamba_shapes(self) -> dict:
        d, di, N, Hs, W, f = self.d, self.di, self.N, self.Hs, self.W, self.f
        return {"ln1/scale": (d,), "ln2/scale": (d,),
                "mamba/in_proj/w": (d, 2 * di + 2 * N), "mamba/dt_proj/w": (d, Hs),
                "mamba/conv_w": (W, di + 2 * N), "mamba/conv_b": (di + 2 * N,),
                "mamba/dt_bias": (Hs,), "mamba/A_log": (Hs,), "mamba/D": (Hs,),
                "mamba/gnorm/scale": (di,), "mamba/out_proj/w": (di, d),
                "mlp/gate/w": (d, f), "mlp/up/w": (d, f), "mlp/down/w": (f, d)}

    def attn_shapes(self) -> dict:
        d, H, K, hd, f = self.d, self.H, self.K, self.hd, self.f
        return {"ln1/scale": (d,), "ln2/scale": (d,),
                "attn/wq/w": (d, H * hd), "attn/wk/w": (d, K * hd),
                "attn/wv/w": (d, K * hd), "attn/wo/w": (H * hd, d),
                "mlp/gate/w": (d, f), "mlp/up/w": (d, f), "mlp/down/w": (f, d)}


# -- weights, drawn again from the seed -----------------------------------------
def layer_weights(key, dims: HDims, stack: str, shapes: dict, layer):
    return {name: WH.draw_layer(key, f"{stack}/{name}", shape, layer, dims.V,
                                dims.dtype).astype(F32)
            for name, shape in shapes.items()}


def embed_table(key, dims: HDims):
    return WH.draw(key, "embed/w", (dims.Vp, dims.d), dims.V, dims.dtype).astype(F32)


# -- the mixers -------------------------------------------------------------------
def mamba(x, w, dims: HDims, quant=None):
    """Mamba2 over x (B, S, d), one position at a time."""
    B, S, _ = x.shape
    di, N, Hs, P, W = dims.di, dims.N, dims.Hs, dims.P, dims.W
    z, xBC = jnp.split(matmul(x, w["mamba/in_proj/w"], quant), [di], axis=-1)
    dt = matmul(x, w["mamba/dt_proj/w"], quant)
    padded = jnp.pad(xBC, ((0, 0), (W - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + S] * w["mamba/conv_w"][i] for i in range(W)) + w["mamba/conv_b"]
    xs, Bm, Cm = jnp.split(jax.nn.silu(conv), [di, di + N], axis=-1)
    xs = xs.reshape(B, S, Hs, P)
    dt = jax.nn.softplus(dt + w["mamba/dt_bias"])
    A = -jnp.exp(w["mamba/A_log"])

    def step(h, inp):
        x_t, B_t, C_t, dt_t = inp
        h = jnp.exp(dt_t * A)[..., None, None] * h \
            + (dt_t[..., None] * x_t)[..., None] * B_t[:, None, None, :]
        return h, jnp.einsum("bn,bhpn->bhp", C_t, h, precision="highest")

    seq = tuple(a.swapaxes(0, 1) for a in (xs, Bm, Cm, dt))
    _, y = jax.lax.scan(step, jnp.zeros((B, Hs, P, N), F32), seq)
    y = y.swapaxes(0, 1) + w["mamba/D"][:, None] * xs
    y = rmsnorm(y.reshape(B, S, di) * jax.nn.silu(z), w["mamba/gnorm/scale"], dims.eps)
    return matmul(y, w["mamba/out_proj/w"], quant)


def attention(x, w, dims: HDims, quant=None, q_chunk=512):
    """Causal GQA self-attention over x (B, S, d) with no position embedding,
    scores times the attention multiplier; exact softmax over all keys, one
    block of query rows at a time so that long sequences fit."""
    B, S, _ = x.shape
    H, K, hd = dims.H, dims.K, dims.hd
    q = matmul(x, w["attn/wq/w"], quant).reshape(B, S, H, hd)
    k = jnp.repeat(matmul(x, w["attn/wk/w"], quant).reshape(B, S, K, hd), H // K, axis=2)
    v = jnp.repeat(matmul(x, w["attn/wv/w"], quant).reshape(B, S, K, hd), H // K, axis=2)
    qc = min(q_chunk, S)
    if S % qc:
        raise ValueError(f"sequence {S} is not a multiple of the query block {qc}")

    def block(i):
        qi = jax.lax.dynamic_slice_in_dim(q, i * qc, qc, axis=1)
        s = jnp.einsum("bqhd,bkhd->bhqk", qi, k, precision="highest") * dims.attn_mult
        rows = i * qc + jnp.arange(qc)[:, None]
        s = jnp.where(jnp.arange(S)[None, :] <= rows, s, -jnp.inf)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v, precision="highest")

    o = jax.lax.map(block, jnp.arange(S // qc))            # (nq, B, qc, H, hd)
    o = o.transpose(1, 0, 2, 3, 4).reshape(B, S, H * hd)
    return matmul(o, w["attn/wo/w"], quant)


@functools.partial(jax.jit, static_argnums=(3, 4, 5))
def _layer(x, key, l, dims: HDims, kind: str, quant):
    if kind == "mamba":
        w = layer_weights(key, dims, "mamba_layers", dims.mamba_shapes(), l)
        mix = mamba(rmsnorm(x, w["ln1/scale"], dims.eps), w, dims, quant)
    else:
        w = layer_weights(key, dims, "attn_layers", dims.attn_shapes(), l)
        mix = attention(rmsnorm(x, w["ln1/scale"], dims.eps), w, dims, quant)
    x = x + dims.res_mult * mix
    return x + dims.res_mult * dense_mlp(rmsnorm(x, w["ln2/scale"], dims.eps), w, quant)


# -- serving: logit gaps of served tokens ---------------------------------------
def served_gaps(key, dims: HDims, tokens, targets, valid, start: int, *, quant=None):
    """As ``reference.served_gaps``: for each sequence in ``tokens`` (N, T),
    the gaps (N, L) by which each target lies below the best logit at
    positions ``start .. start + L - 1`` (0 where ``valid`` is false); with
    ``quant``, the gap of the token the quantised model puts first.

    Runs layer by layer, drawing each layer's weights in the call, so only
    one layer of float32 weights is on the device at a time."""
    table = jax.jit(embed_table, static_argnums=1)(key, dims)
    x = _embed(table, tokens) * dims.emb_mult
    xq = x
    seen = {"mamba": 0, "attention": 0}
    for kind in dims.layer_types:
        l = jnp.int32(seen[kind])
        seen[kind] += 1
        x = _layer(x, key, l, dims, kind, None)
        if quant:
            xq = _layer(xq, key, l, dims, kind, quant)
    return _gaps(x, xq, table, targets, valid, start, dims, quant)


@functools.partial(jax.jit, static_argnums=(5, 6, 7))
def _gaps(x, xq, table, targets, valid, start, dims: HDims, quant):
    L = targets.shape[1]
    norm = jnp.ones((dims.d,), F32)

    def logits(h, q):
        h = rmsnorm(h[:, start:start + L], norm, dims.eps)
        return matmul(h, table.T, q) / dims.logits_scaling

    full = logits(x, None)
    best = full.max(-1)
    pick = jnp.argmax(logits(xq, quant), -1) if quant else targets
    got = jnp.take_along_axis(full, pick[..., None], -1)[..., 0]
    return jnp.where(valid, best - got, 0.0)
