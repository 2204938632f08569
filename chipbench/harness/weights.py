"""Seeded weights, made by the benchmark and handed to the program.

Every leaf is drawn from the run's seed and its own path in the parameter
tree, so the plain reference can draw any leaf, or one layer of a stacked
leaf, again from the seed alone: it never reads what the program holds.

Rules, by the leaf's path:
  * ``.../scale`` (RMSNorm gains): ones;
  * ``.../b`` (biases): zeros;
  * ``embed/w``, ``unembed/w`` (vocab x d): normal * d**-0.5, with the rows
    past the published vocabulary (the program pads it to a multiple of 256)
    set to zero, so a pad id is never the best logit;
  * any other matrix: normal * fan_in**-0.5, fan_in being the next-to-last
    dimension.
Leaves under ``layers/`` are stacked over depth; layer ``l`` of such a leaf
is drawn from its own key, so it can be drawn alone.
The values are rounded to the type the leaf is served in, the config's dtype.
"""

from __future__ import annotations

import zlib

import jax
import jax.numpy as jnp
import numpy as np


def seed_key(seed: int):
    """A PRNG key from any whole number (run seeds may exceed 32 bits)."""
    word = int(np.random.SeedSequence(int(seed)).generate_state(1)[0])
    return jax.random.PRNGKey(word)


def path_str(path) -> str:
    parts = []
    for p in path:
        for attr in ("key", "name", "idx"):
            if hasattr(p, attr):
                parts.append(str(getattr(p, attr)))
                break
        else:
            parts.append(str(p))
    return "/".join(parts)


def draw(key, path: str, shape, vocab: int, dtype):
    """One (unstacked) leaf, rounded to ``dtype``."""
    name = path.rsplit("/", 1)[-1]
    if name in ("scale", "q_norm", "k_norm"):
        return jnp.ones(shape, dtype)
    if name == "b":
        return jnp.zeros(shape, dtype)
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    x = jax.random.normal(k, shape, jnp.float32)
    if path.split("/")[0] in ("embed", "unembed"):
        x = x * shape[-1] ** -0.5
        rows = jnp.arange(shape[0])[:, None] < vocab
        x = jnp.where(rows, x, 0.0)
    else:
        x = x * shape[-2] ** -0.5
    return x.astype(dtype)


def draw_layer(key, path: str, shape, layer, vocab: int, dtype):
    """Layer ``layer`` of a stacked leaf (``shape`` without the depth axis)."""
    return draw(jax.random.fold_in(key, layer), path, shape, vocab, dtype)


def make_params(key, params_like, vocab: int, dtype: str):
    """A tree shaped like ``params_like`` (from ``jax.eval_shape``); meant to
    run inside one ``jax.jit`` so the weights are made on the device."""
    def leaf(path, like):
        p = path_str(path)
        want = jnp.dtype(dtype)
        if like.dtype != want:
            raise ValueError(f"{p}: the program holds {like.dtype}, the "
                             f"benchmark's rule says {want}")
        if p.startswith("layers/"):
            n = like.shape[0]
            return jax.vmap(lambda l: draw_layer(key, p, like.shape[1:], l, vocab, want))(
                jnp.arange(n))
        return draw(key, p, like.shape, vocab, want)

    return jax.tree_util.tree_map_with_path(leaf, params_like)
