"""Per-arch smoke tests (reduced configs): forward/train-step on CPU with
shape checks + finiteness; decode-vs-full-forward consistency; kernel-path
equivalence; MoE behaviours."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCH_IDS, get_config, get_smoke, shapes_for
from repro.models import build_model
from repro.models import transformer
from repro.models.attention import (
    HEAD_ROWS,
    SEQ_MINOR,
    blockwise_sdpa,
    kv_axes,
    kv_order_for,
    kv_stack_shape,
    sdpa,
)
from repro.models.layers import apply_rope, dense, rmsnorm, rope_tables, swiglu
from repro.runtime import (
    RuntimeConfig,
    make_train_state,
    make_train_step,
)

B, S = 2, 32


def _batch(cfg, seq=S, batch=B, with_labels=True):
    rng = jax.random.PRNGKey(7)
    out = {"tokens": jax.random.randint(rng, (batch, seq), 0, cfg.vocab_size)}
    if with_labels:
        out["labels"] = jax.random.randint(rng, (batch, seq), 0, cfg.vocab_size)
    if cfg.family == "vlm":
        out["patch_embeds"] = 0.1 * jax.random.normal(
            rng, (batch, cfg.n_patches, cfg.d_model))
    if cfg.family == "audio":
        out["frames"] = 0.1 * jax.random.normal(
            rng, (batch, cfg.encoder_seq, cfg.d_model))
    return out


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_loss_finite(arch):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    loss, metrics = model.loss(params, _batch(cfg))
    assert np.isfinite(float(loss))
    assert float(loss) > 0


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_smoke_train_step_improves(arch):
    """One-layer-of-substance check: a few SGD-ish steps reduce the loss on a
    repeated batch and produce no NaNs anywhere."""
    cfg = get_smoke(arch)
    model = build_model(cfg)
    rt = RuntimeConfig(remat=None, zero1=False)
    state = make_train_state(model, jax.random.PRNGKey(0), rt)
    step = jax.jit(make_train_step(model, rt))
    batch = _batch(cfg)
    losses = []
    for _ in range(5):
        state, m = step(state, batch)
        losses.append(float(m["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0]
    for leaf in jax.tree.leaves(state.params):
        assert bool(jnp.all(jnp.isfinite(leaf.astype(jnp.float32))))


# a dense smoke config whose every layer attends within a window of 8, shorter
# than the prompt: the window mask over the carried stacks
WINDOWED = "phi4-mini-3.8b+window8"


def _smoke(arch):
    if arch == WINDOWED:
        return dataclasses.replace(get_smoke("phi4-mini-3.8b"), sliding_window=8)
    return get_smoke(arch)


@pytest.mark.parametrize("arch", ARCH_IDS + (WINDOWED,))
def test_decode_matches_full_forward(arch):
    cfg = _smoke(arch)
    if cfg.family == "moe":
        cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)  # no drops
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_pref = cfg.n_patches if cfg.family == "vlm" else 0
    S_max = S + 4 + n_pref
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 4), 0, cfg.vocab_size)
    batch = dict(_batch(cfg, with_labels=False), tokens=toks[:, :S])
    _, cache = model.prefill(params, batch, S_max)
    for t in range(4):
        logits, cache = model.decode_step(params, cache, {"token": toks[:, S + t]})
    full_logits, _ = model.prefill(params, dict(batch, tokens=toks), S_max)
    scale = float(jnp.max(jnp.abs(full_logits))) + 1e-9
    err = float(jnp.max(jnp.abs(logits - full_logits)))
    assert err / scale < 2e-2, (arch, err, scale)


# every family with attention keeps one (L, ...) stack per k and v in
# kv_order_for(hd), carried through the decode scan: L layers, Zamba2's
# invocation sites of its shared block, granite-H's attention layers
KV_STACKS = [a for a in ARCH_IDS if get_smoke(a).family != "ssm"]


@pytest.mark.parametrize("arch", KV_STACKS)
def test_decode_cache_matches_prefill(arch):
    """Four decode steps write the rows a prefill of the whole sequence
    writes, in the stacks' layout, and leave the rows after them zero."""
    cfg = get_smoke(arch)
    if cfg.family == "moe":
        cfg = dataclasses.replace(cfg, moe_capacity_factor=8.0)  # no drops
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    n_pref = cfg.n_patches if cfg.family == "vlm" else 0
    S_max = S + 8 + n_pref
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 4), 0, cfg.vocab_size)
    batch = dict(_batch(cfg, with_labels=False), tokens=toks[:, :S])
    _, cache = model.prefill(params, batch, S_max)
    for t in range(4):
        _, cache = model.decode_step(params, cache, {"token": toks[:, S + t]})
    _, full = model.prefill(params, dict(batch, tokens=toks), S_max)
    n = n_pref + S + 4
    assert int(cache["pos"]) == int(full["pos"]) == n
    back = _stack_to_rows(cfg.hd)
    for name in ("k", "v"):
        assert cache[name].shape == model.init_cache(B, S_max)[name].shape
        got = np.asarray(cache[name]).transpose(back)
        want = np.asarray(full[name]).transpose(back)
        assert got.shape == (cache[name].shape[0], B, S_max, cfg.n_kv_heads, cfg.hd)
        err = np.max(np.abs(got[:, :, :n] - want[:, :, :n]))
        assert err / np.max(np.abs(want[:, :, :n])) < 2e-2, (arch, name, err)
        assert not np.any(got[:, :, n:]), (arch, name)


def _stack_to_rows(hd):
    """The permutation that views (L, ...) stacks in ``kv_order_for(hd)`` as
    (L, B, S, K, hd)."""
    order = kv_order_for(hd)
    return (0,) + tuple(1 + order.index(a) for a in range(4))


def test_kv_order_follows_the_head_width():
    """Heads that fill the 128 lanes keep rows of hd per (b, k); narrower
    heads put the sequence on the lanes."""
    assert kv_order_for(128) == kv_order_for(256) == HEAD_ROWS
    assert kv_order_for(64) == kv_order_for(32) == SEQ_MINOR
    assert kv_stack_shape(32, 24, 1024, 8, 128) == (32, 24, 8, 1024, 128)
    assert kv_stack_shape(4, 24, 4608, 8, 64) == (4, 24, 8, 64, 4608)
    assert kv_axes(128) == (1, 3, 2)
    assert kv_axes(64) == (1, 4, 2)


def _plain_decode_layer(lp, x, cfg, k, v, pos):
    """One dense layer's decode step with its cache as (B, S, K, hd) arrays,
    written by dynamic_update_slice and read by ``sdpa``."""
    B, H, K, hd = x.shape[0], cfg.n_heads, cfg.n_kv_heads, cfg.hd
    xn = rmsnorm(lp["ln1"], x, cfg.norm_eps)
    q = dense(lp["attn"]["wq"], xn).reshape(B, 1, H, hd)
    kn = dense(lp["attn"]["wk"], xn).reshape(B, 1, K, hd)
    vn = dense(lp["attn"]["wv"], xn).reshape(B, 1, K, hd)
    cos, sin = rope_tables(pos[None], hd, cfg.rope_theta)
    q, kn = apply_rope(q, cos, sin), apply_rope(kn, cos, sin)
    k = jax.lax.dynamic_update_slice(k, kn, (0, pos, 0, 0))
    v = jax.lax.dynamic_update_slice(v, vn, (0, pos, 0, 0))
    out = sdpa(q, k, v, q_offset=pos, kv_len=pos + 1)
    x = x + dense(lp["attn"]["wo"], out.reshape(B, 1, H * hd))
    return x + swiglu(lp["mlp"], rmsnorm(lp["ln2"], x, cfg.norm_eps)), k, v


def test_head_rows_decode_matches_plain_sdpa():
    """Heads of 128 in phi4's 3:1 grouping, 2 layers: four decode steps over
    the HEAD_ROWS stacks give the logits of a plain decode that keeps each
    layer's cache as (B, S, K, hd) and attends with ``sdpa``."""
    cfg = dataclasses.replace(get_smoke("phi4-mini-3.8b"), n_layers=2, n_heads=6,
                              n_kv_heads=2, head_dim=128)
    assert kv_order_for(cfg.hd) == HEAD_ROWS
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    S_max = S + 8
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 4), 0, cfg.vocab_size)
    _, cache = model.prefill(params, {"tokens": toks[:, :S]}, S_max)
    assert cache["k"].shape == (2, B, cfg.n_kv_heads, S_max, cfg.hd)
    back = _stack_to_rows(cfg.hd)
    ks = list(cache["k"].transpose(back))
    vs = list(cache["v"].transpose(back))
    for t in range(4):
        tok = toks[:, S + t]
        logits, cache = model.decode_step(params, cache, {"token": tok})
        pos = jnp.int32(S + t)
        x = transformer.embed(params["embed"], tok[:, None])
        for i in range(cfg.n_layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            x, ks[i], vs[i] = _plain_decode_layer(lp, x, cfg, ks[i], vs[i], pos)
        want = transformer._logits(params, cfg, transformer._final_norm(params, cfg, x)[:, 0])
        np.testing.assert_allclose(np.asarray(logits), np.asarray(want), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("hd", [32, 128])
def test_decode_kernel_path_matches_jnp(hd):
    """The Pallas decode kernel (interpreted here) reads the layer's slice of
    the stacks as the jnp attention does, in either order (SEQ_MINOR for
    heads of 32, HEAD_ROWS for heads of 128)."""
    cfg = dataclasses.replace(get_smoke("phi4-mini-3.8b"), head_dim=hd)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    S_max = S + 32
    toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 4), 0, cfg.vocab_size)
    _, cache = model.prefill(params, _batch(cfg, with_labels=False) | {"tokens": toks[:, :S]}, S_max)
    ref = ker = cache
    for t in range(4):
        tok = {"token": toks[:, S + t]}
        l_ref, ref = model.decode_step(params, ref, tok)
        l_ker, ker = model.decode_step(params, ker, tok, use_kernels=True)
        scale = float(jnp.max(jnp.abs(l_ref)))
        err = float(jnp.max(jnp.abs(l_ker - l_ref)))
        assert err / scale < 1e-4, (t, err, scale)
    np.testing.assert_allclose(np.asarray(ker["k"]), np.asarray(ref["k"]), rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("arch", ["phi4-mini-3.8b", "zamba2-7b", "granite-4.0-h-micro"])
def test_kernel_path_matches_reference(arch):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, seq=64)
    l_ref, _ = model.loss(params, batch)
    l_ker, _ = model.loss(params, batch, use_kernels=True)
    assert abs(float(l_ref) - float(l_ker)) < 1e-4


def test_output_logits_shape_padded_vocab():
    cfg = get_smoke("internvl2-2b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    batch = _batch(cfg, with_labels=False)
    logits, cache = model.prefill(params, batch, S + cfg.n_patches + 2)
    assert logits.shape == (B, cfg.padded_vocab)
    assert cfg.padded_vocab % 256 == 0


def test_moe_capacity_drops_tokens():
    cfg = dataclasses.replace(get_smoke("granite-moe-1b-a400m"),
                              moe_capacity_factor=0.25)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    loss, _ = model.loss(params, _batch(cfg))
    assert np.isfinite(float(loss))  # drops degrade, never break


def test_moe_aux_loss_positive():
    cfg = get_smoke("qwen3-moe-30b-a3b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    _, metrics = model.loss(params, _batch(cfg))
    assert float(metrics["aux"]) >= 1.0  # >= 1 by Cauchy-Schwarz, = 1 balanced


def test_blockwise_equals_dense_attention():
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (2, 256, 4, 32))
    k = jax.random.normal(ks[1], (2, 256, 2, 32))
    v = jax.random.normal(ks[2], (2, 256, 2, 32))
    for w in (None, 100):
        a = sdpa(q, k, v, causal=True, window=w)
        b = blockwise_sdpa(q, k, v, causal=True, window=w, q_chunk=64, k_chunk=128)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5, rtol=2e-5)


def test_param_count_matches_configs():
    """Analytic param_count (used for roofline MODEL_FLOPS) tracks actual
    init within 12% for dense archs (padding + analytic approximations), and
    for both hybrids (granite-4.0-h's layer pattern, zamba2's shared block)."""
    for arch in ("phi4-mini-3.8b", "qwen3-14b", "granite-4.0-h-micro", "zamba2-7b"):
        cfg = get_smoke(arch)
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
        actual = sum(p.size for p in jax.tree.leaves(params))
        assert abs(actual - cfg.param_count()) / actual < 0.12


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_input_specs_cover_all_shapes(arch):
    model = build_model(get_config(arch))
    for shape in shapes_for(arch):
        specs = model.input_specs(shape)
        assert specs, (arch, shape.name)
        for k, v in specs.items():
            assert isinstance(v, jax.ShapeDtypeStruct)
            assert v.shape[0] == shape.global_batch
