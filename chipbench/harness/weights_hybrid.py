"""Seeded weights of a Mamba2 hybrid, made by the benchmark and handed to the
program, by the rules of ``weights.py`` with these for the mixer's leaves:

  * ``A_log`` (per head): log U[1, 16], so A = -exp(A_log) lies in [-16, -1];
  * ``dt_bias`` (per head): softplus^-1 of exp(U[ln 1e-3, ln 0.1]), so that
    the step dt = softplus(dt_raw + dt_bias) starts in [1e-3, 0.1];
  * ``D`` (per head): ones;
  * ``conv_b``: zeros; ``conv_w`` (W, channels) is a matrix under the rule of
    ``weights.py``: normal / sqrt(W);
  * ``embed/w``: the rule of ``weights.py`` times ``EMBED_SCALE``.

The mixer's are Mamba2's published init ranges. The embedding is smaller than
``weights.py`` draws it: the table is tied and the embeddings are multiplied
by 12, so at unit-norm rows a token's own row would outscore every other row
in the logits by a wide margin whatever the layers compute, greedy decoding
would repeat the prompt's last token, and no fault in the layers could show
in the served tokens. At a tenth, the layers decide the next token.

The per-head scalars are rounded to bfloat16 and held in float32, as the
program holds them; every other leaf is in the configuration's dtype. Leaves under ``mamba_layers/`` and
``attn_layers/`` are stacked over their layers, and layer ``l`` of such a
leaf is drawn from its own key, so the reference draws any layer alone.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp

from . import weights as W

STACKS = ("mamba_layers/", "attn_layers/")
EMBED_SCALE = 0.1
HEAD_SCALARS = ("A_log", "dt_bias", "D")


def draw(key, path: str, shape, vocab: int, dtype):
    """One (unstacked) leaf: the per-head scalars in float32, the rest in
    ``dtype``."""
    name = path.rsplit("/", 1)[-1]
    if name == "conv_b":
        return jnp.zeros(shape, dtype)
    if path == "embed/w":
        return (W.draw(key, path, shape, vocab, jnp.float32) * EMBED_SCALE).astype(dtype)
    if name not in HEAD_SCALARS:
        return W.draw(key, path, shape, vocab, dtype)
    u = jax.random.uniform(jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF),
                           shape, jnp.float32)
    if name == "A_log":
        x = jnp.log(1.0 + 15.0 * u)
    elif name == "dt_bias":
        dt = jnp.exp(jnp.log(1e-3) + u * (jnp.log(0.1) - jnp.log(1e-3)))
        x = dt + jnp.log(-jnp.expm1(-dt))          # softplus^-1(dt)
    else:
        x = jnp.ones(shape, jnp.float32)
    return x.astype(jnp.bfloat16).astype(jnp.float32)


def draw_layer(key, path: str, shape, layer, vocab: int, dtype):
    """Layer ``layer`` of a stacked leaf (``shape`` without the layer axis)."""
    return draw(jax.random.fold_in(key, layer), path, shape, vocab, dtype)


def make_params(key, params_like, vocab: int, dtype: str):
    """A tree shaped like ``params_like`` (from ``jax.eval_shape``); meant to
    run inside one ``jax.jit`` so the weights are made on the device."""
    def leaf(path, like):
        p = W.path_str(path)
        want = jnp.float32 if p.rsplit("/", 1)[-1] in HEAD_SCALARS else jnp.dtype(dtype)
        if like.dtype != want:
            raise ValueError(f"{p}: the program holds {like.dtype}, the "
                             f"benchmark's rule says {jnp.dtype(want)}")
        if p.startswith(STACKS):
            return jax.vmap(lambda l: draw_layer(key, p, like.shape[1:], l, vocab, dtype))(
                jnp.arange(like.shape[0]))
        return draw(key, p, like.shape, vocab, dtype)

    return jax.tree_util.tree_map_with_path(leaf, params_like)
