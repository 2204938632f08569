"""granite-4.0-h against the plain float32 reference (``granite_h_reference``),
on seeded random weights at the smoke size (one whole period of the layer
pattern), on the CPU.

The program runs the smoke config in float32 here, so the only differences
from the reference are the order of float32 arithmetic (the chunked SSD
against the one-step recurrence): 2.6e-7 of the largest logit. ``TOL`` is
1e-5 of it, some forty times that, and under what each planted fault does:
rounding the carried SSM state to bfloat16 between decode steps moves the
served logits by 1.3e-4 of the largest, dropping the attention multiplier by
1.6e-2, and dropping the embedding or residual multiplier or the logit
scaling by 0.7 or more.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import granite_h_reference as ref
from repro.configs import get_config, get_smoke
from repro.models import build_model
from repro.models.hybrid import granite_forward
from repro.models.mamba2 import ssd_chunked

CFG = get_smoke("granite-4.0-h-micro")
TOL = 1e-5          # of the largest reference logit; see the module docstring
B, P, STEPS = 2, 40, 16


def _params(cfg, seed=0):
    """The program's initial weights, with the per-head scalars in the
    published init ranges (A in [1, 16], dt in [1e-3, 0.1]), so that the
    state carries over many positions."""
    params = build_model(cfg).init(jax.random.PRNGKey(seed))
    m = params["mamba_layers"]["mamba"]
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed + 1))
    A = jax.random.uniform(k1, m["A_log"].shape, minval=1.0, maxval=16.0)
    dt = jnp.exp(jax.random.uniform(k2, m["dt_bias"].shape,
                                    minval=np.log(1e-3), maxval=np.log(0.1)))
    m = dict(m, A_log=jnp.log(A), dt_bias=dt + jnp.log(-jnp.expm1(-dt)))
    return dict(params, mamba_layers=dict(params["mamba_layers"], mamba=m))


def _tokens(n, seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), (B, n), 0, CFG.vocab_size)


@pytest.fixture(scope="module")
def case():
    """Weights, a sequence of P + STEPS tokens, and the reference's logits."""
    params = _params(CFG)
    toks = _tokens(P + STEPS)
    return params, toks, np.asarray(ref.logits(params, toks, CFG))


def _served(cfg, params, toks, round_state=False, use_kernels=False):
    """Logits at positions P-1 .. P+STEPS-1 from a prefill of P tokens and
    STEPS decode steps through the cache."""
    model = build_model(cfg)
    logits, cache = jax.jit(lambda p, t: model.prefill(
        p, {"tokens": t}, P + STEPS, use_kernels=use_kernels))(params, toks[:, :P])
    step = jax.jit(functools.partial(model.decode_step, use_kernels=use_kernels))
    out = [logits]
    for t in range(STEPS):
        if round_state:
            cache = dict(cache, h=cache["h"].astype(jnp.bfloat16).astype(jnp.float32))
        logits, cache = step(params, cache, {"token": toks[:, P + t]})
        out.append(logits)
    return np.stack([np.asarray(o) for o in out], axis=1)[:, :STEPS]


def _err(got, want):
    return float(np.max(np.abs(got - want))) / float(np.max(np.abs(want)))


def test_full_forward_matches_reference(case):
    params, toks, want = case
    got = np.asarray(jax.jit(lambda p, t: granite_forward(p, t, CFG))(params, toks))
    assert _err(got, want) < TOL


@pytest.mark.parametrize("use_kernels", [False, True])
def test_prefill_then_decode_matches_reference(case, use_kernels):
    """Through the jnp path, and through the Pallas kernels (interpreted
    here): flash attention and the SSD's intra-chunk kernel in the prefill,
    the decode attention kernel over the SEQ_MINOR stacks."""
    params, toks, want = case
    got = _served(CFG, params, toks, use_kernels=use_kernels)
    assert _err(got, want[:, P - 1:P - 1 + STEPS]) < TOL


NEUTRAL = {"embedding_multiplier": 1.0, "residual_multiplier": 1.0,
           "attention_multiplier": None, "logits_scaling": 1.0}


@pytest.mark.parametrize("fault", [*NEUTRAL, "bf16_state"])
def test_tolerance_catches_fault(case, fault):
    """Each planted fault moves the served logits past ``TOL``."""
    params, toks, want = case
    if fault == "bf16_state":
        got = _served(CFG, params, toks, round_state=True)
    else:
        got = _served(dataclasses.replace(CFG, **{fault: NEUTRAL[fault]}), params, toks)
    assert _err(got, want[:, P - 1:P - 1 + STEPS]) > TOL


def test_ssd_chunked_matches_recurrence():
    """The chunked scan against the one-step recurrence at a length the chunk
    does not divide (45 = 2 x 16 + 13), output and final state."""
    Bsz, S, H, Pd, N, Q = 2, 45, 3, 8, 16, 16
    ks = jax.random.split(jax.random.PRNGKey(3), 5)
    x = jax.random.normal(ks[0], (Bsz, S, H, Pd))
    dt = jnp.exp(jax.random.uniform(ks[1], (Bsz, S, H), minval=np.log(1e-3),
                                    maxval=np.log(0.5)))
    A = -jax.random.uniform(ks[2], (H,), minval=1.0, maxval=16.0)
    Bm = jax.random.normal(ks[3], (Bsz, S, N))
    Cm = jax.random.normal(ks[4], (Bsz, S, N))
    y, h = ssd_chunked(x, dt, A, Bm, Cm, Q)
    y_ref, h_ref = ref.ssd_recurrence(x, dt, A, Bm, Cm)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(h), np.asarray(h_ref), rtol=1e-5, atol=1e-5)


def test_two_periods_match_reference():
    """Two periods of the pattern (the group scan's second pass reads the
    second slices of every stack)."""
    cfg = dataclasses.replace(CFG, n_layers=20, layer_types=CFG.layer_types * 2)
    params = _params(cfg, seed=5)
    toks = _tokens(P + STEPS, seed=6)
    want = np.asarray(ref.logits(params, toks, cfg))
    assert _err(_served(cfg, params, toks), want[:, P - 1:P - 1 + STEPS]) < TOL


def test_config_is_the_published_one():
    cfg = get_config("granite-4.0-h-micro")
    assert (cfg.n_layers, cfg.d_model, cfg.d_ff, cfg.vocab_size) == (40, 2048, 8192, 100352)
    assert [i for i, t in enumerate(cfg.layer_types) if t == "attention"] == [5, 15, 25, 35]
    assert (cfg.n_ssm_heads, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_chunk) == (64, 64, 128, 256)
    assert cfg.nope and cfg.tie_embeddings and cfg.hd == 64
    assert 3.1e9 < cfg.param_count() < 3.3e9
    # the smoke variant keeps one whole period
    assert CFG.layer_types == cfg.layer_types[:10]
