"""What the launch entry points share: the compile cache, the device label
printed beside every timing, and the model config with an optional depth cut.
"""

from __future__ import annotations

import dataclasses
import os

import jax

from ..configs import ModelConfig, get_config, get_smoke

# The cache directory is part of the cache key, so it is one fixed path.
REPO_CACHE_DIR = os.path.abspath(
    os.path.join(os.path.dirname(__file__), "..", "..", "..", ".jax_cache"))


def enable_compile_cache() -> None:
    """Keep compiled programs across processes (a full-width model compiles
    for a minute or more). Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX
    reads it and nothing is set here; otherwise the cache is
    ``<checkout>/.jax_cache``, which git ignores."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)


def device_label(n: int | None = None) -> str:
    """What a printed timing ran on, as JAX reports it: platform, device
    kind and device count (``n`` of them, by default all)."""
    devs = jax.devices()
    return f"{devs[0].platform}:{devs[0].device_kind} x{n or len(devs)}"


def model_config(arch: str, *, full: bool, layers: int | None = None) -> ModelConfig:
    """The published config (``full``) or its smoke variant, with the depth
    optionally cut to ``layers``. Widths never change; the cut is printed."""
    cfg = get_config(arch) if full else get_smoke(arch)
    if layers is None or layers == cfg.n_layers:
        return cfg
    period = max(1, cfg.shared_attn_every, cfg.slstm_period,
                 cfg.n_layers // cfg.layer_types.count("attention") if cfg.layer_types else 1)
    if not 0 < layers <= cfg.n_layers or layers % period:
        raise ValueError(f"{cfg.name}: cannot cut {cfg.n_layers} layers to {layers} "
                         f"(needs 1..{cfg.n_layers}, a multiple of {period})")
    print(f"[config] {cfg.name}: depth cut {cfg.n_layers} -> {layers} layers, "
          "widths unchanged")
    return dataclasses.replace(cfg, n_layers=layers, name=f"{cfg.name}-{layers}l",
                               layer_types=cfg.layer_types[:layers])
