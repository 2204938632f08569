"""Sharding rules: pytree paths -> PartitionSpecs for the (pod, data, model)
mesh.

Tensor parallelism rides the "model" axis (attention/FFN inner dims, vocab,
MoE experts, SSM inner channels); data parallelism rides ("pod", "data").
Rules are *candidate lists*: the first assignment whose axis sizes divide the
dimension wins, axes that do not divide are dropped (e.g. internvl2's odd
92553 vocab falls back from vocab- to d_model-sharding). Stacked-layer
leading dims are padded with None automatically.
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..models.attention import kv_axes

MODEL = "model"


def dp_axes(mesh: Mesh) -> tuple[str, ...]:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _axis_size(mesh: Mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return math.prod(mesh.shape[a] for a in axes)


def _sanitize(mesh: Mesh, spec: Sequence, shape: tuple[int, ...]) -> P:
    """Drop axis assignments that don't divide the dim; composite dp axes
    degrade to their largest dividing prefix."""
    out = []
    for dim, axes in zip(shape, spec):
        if axes is None:
            out.append(None)
            continue
        cand = axes if isinstance(axes, tuple) else (axes,)
        # try full composite, then prefixes, then single axes
        chosen = None
        options = [cand] + [cand[:i] for i in range(len(cand) - 1, 0, -1)] + [
            (a,) for a in cand
        ]
        for opt in options:
            if dim % _axis_size(mesh, opt) == 0:
                chosen = opt if len(opt) > 1 else opt[0]
                break
        out.append(chosen)
    # an axis may appear at most once in the spec
    seen = set()
    final = []
    for axes in out:
        cand = axes if isinstance(axes, tuple) else ((axes,) if axes else ())
        if any(a in seen for a in cand):
            final.append(None)
            continue
        seen.update(cand)
        final.append(axes)
    return P(*final)


# -- parameter rules ----------------------------------------------------------
# (substring match on the '/'-joined path, logical spec for the trailing dims)
_PARAM_RULES: list[tuple[str, tuple]] = [
    ("router/w", (None, None)),
    ("moe/gate", (MODEL, None, None)),       # (E, d, f): expert parallel
    ("moe/up", (MODEL, None, None)),
    ("moe/down", (MODEL, None, None)),
    ("embed/w", (MODEL, None)),
    ("unembed/w", (MODEL, None)),
    ("wq/w", (None, MODEL)),
    ("wk/w", (None, MODEL)),
    ("wv/w", (None, MODEL)),
    ("wq/b", (MODEL,)),
    ("wk/b", (MODEL,)),
    ("wv/b", (MODEL,)),
    ("wo/w", (MODEL, None)),
    ("gate/w", (None, MODEL)),
    ("up/w", (None, MODEL)),
    ("down/w", (MODEL, None)),
    ("in_proj/w", (None, MODEL)),
    ("dt_proj/w", (None, MODEL)),
    ("out_proj/w", (MODEL, None)),
    ("conv_w", (None, MODEL)),
    ("conv_b", (MODEL,)),
    ("gnorm/scale", (MODEL,)),
    ("w_in/w", (None, MODEL)),
    ("w_in/b", (MODEL,)),
    ("w_gates/w", (None, MODEL)),
    ("w_gates/b", (MODEL,)),
    ("skip", (MODEL,)),
    ("R", (None, MODEL, None, None)),        # (4, H, dh, dh)
]

# how many leading stacked-layer dims each top-level group carries
_STACK_DIMS = {
    "layers": 1,
    "mamba_groups": 2,
    "mamba_tail": 1,
    "mamba_layers": 1,
    "attn_layers": 1,
    "shared": 0,
    "slstm": 1,
    "mlstm": 2,
    "enc_layers": 1,
    "dec_layers": 1,
}


def _path_str(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def param_spec(mesh: Mesh, path_str: str, shape: tuple[int, ...]) -> P:
    top = path_str.split("/")[0]
    n_stack = _STACK_DIMS.get(top, 0)
    logical_shape = shape[n_stack:]
    for pat, spec in _PARAM_RULES:
        if pat in path_str and len(spec) == len(logical_shape):
            full = (None,) * n_stack + tuple(spec)
            return _sanitize(mesh, full, shape)
    return P()  # replicate (norm scales, small vectors, ...)


def param_shardings(mesh: Mesh, params_like):
    def one(path, leaf):
        return NamedSharding(mesh, param_spec(mesh, _path_str(path), leaf.shape))

    return jax.tree_util.tree_map_with_path(one, params_like)


# -- optimizer state ----------------------------------------------------------
def opt_shardings(mesh: Mesh, opt_like, params_sharding, *, zero1: bool = False):
    """m/v/master shadow the param shardings; with zero1, additionally shard
    the largest unsharded dim over "data" (optimizer-state partitioning)."""
    dp = tuple(a for a in mesh.axis_names if a == "data")

    def shadow(ps, leaf):
        spec = list(ps.spec) + [None] * (len(leaf.shape) - len(ps.spec))
        if zero1 and dp:
            used = {a for s in spec if s for a in ((s,) if isinstance(s, str) else s)}
            if "data" not in used:
                # biggest dim not already sharded, divisible by data axis
                order = np.argsort([-d for d in leaf.shape])
                for i in order:
                    if spec[i] is None and leaf.shape[i] % mesh.shape["data"] == 0:
                        spec[i] = "data"
                        break
        return NamedSharding(mesh, P(*spec))

    import jax.tree_util as jtu

    def one(ps_leaf, leaf):
        return shadow(ps_leaf, leaf)

    # opt state = OptState(step, master, m, v) with same tree structure in
    # master/m/v as params
    from ..optim.adamw import OptState

    step_sh = NamedSharding(mesh, P())
    master = jax.tree.map(one, params_sharding, opt_like.master)
    m = jax.tree.map(one, params_sharding, opt_like.m)
    v = jax.tree.map(one, params_sharding, opt_like.v)
    return OptState(step=step_sh, master=master, m=m, v=v)


# -- activations / batches / caches ------------------------------------------
def batch_shardings(mesh: Mesh, batch_like):
    dp = dp_axes(mesh)

    def one(path, leaf):
        spec = [None] * len(leaf.shape)
        if len(leaf.shape) >= 1 and leaf.shape[0] % _axis_size(mesh, dp) == 0:
            spec[0] = dp if len(dp) > 1 else dp[0]
        return NamedSharding(mesh, _sanitize(mesh, spec, leaf.shape))

    return jax.tree_util.tree_map_with_path(one, batch_like)


def cache_shardings(mesh: Mesh, cache_like, cfg):
    """Decode caches, matched by the name of each top-level entry. Every
    entry shards its batch over dp where that divides.

    * ``k``/``v``, every family's (L, ...) KV stacks (``kv_axes``): the
      sequence over "model" (flash-decode style context parallelism, head-count
      agnostic); granite-H's heads instead.
    * ``ck``/``cv``, whisper's cross-attention K/V (L, B, T, K, hd): T over
      "model".
    * Mamba2 states ``h``/``g_h``/``t_h`` (..., B, H, P, N): heads over
      "model". Conv windows: granite-H's ``conv`` (L, W-1, B, Ch), Zamba2's
      ``g_conv``/``t_conv`` (..., B, W-1, Ch).
    * xLSTM states, the tuples ``s`` (nb, B, ...) and ``m`` (nb, nm, B, ...):
      batch-local. Any model-axis sharding of the mLSTM matrix memory loses:
      GSPMD cannot reshard between the layouts the decode einsums prefer and
      replicates the whole cache every step (measured 113 -> 254 ms/step).
    """
    dp = dp_axes(mesh)
    kv = kv_axes(cfg.hd)
    kv_model = kv.heads if cfg.layer_types else kv.seq

    def one(path, leaf):
        name = path[0].key
        spec: list = [None] * leaf.ndim
        if name in ("k", "v"):
            spec[kv.batch], spec[kv_model] = dp, MODEL
        elif name in ("ck", "cv"):
            spec[1], spec[2] = dp, MODEL
        elif name in ("h", "g_h", "t_h"):
            spec[-4], spec[-3] = dp, MODEL
        elif name == "conv":
            spec[2] = dp
        elif name in ("g_conv", "t_conv"):
            spec[-3] = dp
        elif name == "s":
            spec[1] = dp
        elif name == "m":
            spec[2] = dp
        return NamedSharding(mesh, _sanitize(mesh, spec, leaf.shape))

    return jax.tree_util.tree_map_with_path(one, cache_like)


def replicated(mesh: Mesh, tree_like):
    return jax.tree.map(lambda _: NamedSharding(mesh, P()), tree_like)
