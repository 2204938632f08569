"""Every Pallas kernel compiles natively for a TPU v5e at published widths,
the decode step keeps its KV cache in place, and granite-4.0-h-micro's
serve steps fit one chip at the batch its benchmark cell serves.

Nothing runs: the TPU compiler, which is installed with jaxlib, compiles for
a described v5e chip that is not attached. That catches what interpret mode
cannot, such as blocks that break the chip's (8, 128) tiling. Each kernel
test asserts that the kernel reached the compiled program as a Mosaic custom
call.
"""

import dataclasses
import functools
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.experimental.compilation_cache import compilation_cache
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs import get_config
from repro.kernels import decode_attention as _dec
from repro.kernels import flash_attention as _fa
from repro.kernels import rmsnorm as _rn
from repro.kernels import ssd_scan as _ssd
from repro.models import build_model
from repro.launch.serve import cache_len
from repro.runtime import RuntimeConfig, jit_decode_step, jit_prefill

PHI4 = get_config("phi4-mini-3.8b")
GRANITE_H = get_config("granite-4.0-h-micro")
ZAMBA2 = get_config("zamba2-7b")
WHISPER = get_config("whisper-tiny")
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # any failure means no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the persistent
    # cache, so keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compiled_text(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


@pytest.mark.parametrize("S", [2048, 512])
def test_flash_attention_compiles_phi4(one_chip, S):
    B, H, K, hd = 1, PHI4.n_heads, PHI4.n_kv_heads, PHI4.hd
    fn = functools.partial(_fa.flash_attention, causal=True, interpret=False)
    text = _compiled_text(fn, one_chip, ((B, S, H, hd), BF16),
                          ((B, S, K, hd), BF16), ((B, S, K, hd), BF16))
    assert "tpu_custom_call" in text


def test_decode_attention_compiles_phi4(one_chip):
    B, T, H, K, hd = 8, 2048, PHI4.n_heads, PHI4.n_kv_heads, PHI4.hd
    fn = functools.partial(_dec.decode_attention, kv_len=1000, interpret=False)
    text = _compiled_text(fn, one_chip, ((B, 1, H, hd), BF16),
                          ((B, T, K, hd), BF16), ((B, T, K, hd), BF16))
    assert "tpu_custom_call" in text


def test_rmsnorm_compiles_phi4(one_chip):
    d = PHI4.d_model
    fn = functools.partial(_rn.rmsnorm, interpret=False)
    text = _compiled_text(fn, one_chip, ((8, 512, d), BF16), ((d,), BF16))
    assert "tpu_custom_call" in text


def test_ssd_intra_chunk_compiles_zamba2(one_chip):
    B, nc, Q = 1, 4, ZAMBA2.ssm_chunk
    H, N, P = ZAMBA2.n_ssm_heads, ZAMBA2.ssm_state, ZAMBA2.ssm_head_dim
    fn = functools.partial(_ssd.ssd_intra_chunk, interpret=False)
    f32 = jnp.float32
    text = _compiled_text(fn, one_chip, ((B, nc, Q, H), f32), ((B, nc, Q, N), BF16),
                          ((B, nc, Q, N), BF16), ((B, nc, Q, H, P), BF16))
    assert "tpu_custom_call" in text


@pytest.mark.parametrize("cfg, B, S_max", [
    pytest.param(dataclasses.replace(PHI4, n_layers=2), 8, 256, id="8-256"),
    pytest.param(dataclasses.replace(PHI4, n_layers=2), 2, 256, id="2-256"),
    # two groups of 6 mamba layers and the shared attention block: a stack of
    # one entry would not show a restacking copy
    pytest.param(dataclasses.replace(ZAMBA2, n_layers=12), 8, 256, id="zamba2-7b"),
    pytest.param(WHISPER, 8, 256, id="whisper-tiny"),
])
def test_decode_step_keeps_cache_in_place(one_chip, cfg, B, S_max):
    """At published widths (phi4-mini cut to 2 layers at a batch of 8 and of
    2, phi4-longdoc's; Zamba2 cut to two groups; whisper-tiny) the decode
    step copies no whole KV stack and needs no temporary as large as one
    layer's K slice. Zamba2's mamba states still pass through its scans as
    inputs and outputs, so its temporaries are not bounded."""
    model = build_model(cfg)
    params_like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    cache_like = jax.eval_shape(lambda: model.init_cache(B, S_max))
    tok_like = {"token": jax.ShapeDtypeStruct((B,), jnp.int32)}
    mesh = Mesh(np.array([*one_chip.device_set]).reshape(1, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    step, *_ = jit_decode_step(model, mesh, RuntimeConfig(), params_like,
                               cache_like, tok_like)
    compiled = step.lower(params_like, cache_like, tok_like).compile()
    layer_k_bytes = S_max * cfg.n_kv_heads * B * cfg.hd * 2
    if cfg.family != "hybrid":
        assert compiled.memory_analysis().temp_size_in_bytes < layer_k_bytes
    stack = "bf16[" + ",".join(map(str, cache_like["k"].shape)) + "]"
    # copy ops and copy fusions of a whole stack: a scan over the stacks as
    # xs and ys makes four a step, restacking the ys and copying them out;
    # a loop that wants the stacks in another layout copies them in and out
    copies = re.findall(r"%(copy[\w.\-]*) = " + re.escape(stack), compiled.as_text())
    assert not copies, copies


def test_granite_h_serve_steps_fit_one_chip(one_chip):
    """granite-4.0-h-micro at full size, at the benchmark cell's shapes (24
    sequences, prompts of 4096, up to 512 new tokens): prefill and decode
    each fit the chip's 15.75 GB, the KV stacks of its heads of 64 are
    SEQ_MINOR, and the decode step's temporaries are smaller than one Mamba
    layer's state slice, so neither the SSM state nor the KV stacks are
    copied."""
    cfg, B, P = GRANITE_H, 24, 4096
    S_max = cache_len(P + 512)
    model = build_model(cfg)
    params_like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch_like = {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)}
    cache_like = jax.eval_shape(lambda p, b: model.prefill(p, b, S_max), params_like,
                                batch_like)[1]
    assert cache_like["k"].shape == (4, B, cfg.n_kv_heads, cfg.hd, S_max)  # SEQ_MINOR
    tok_like = {"token": jax.ShapeDtypeStruct((B,), jnp.int32)}
    mesh = Mesh(np.array([*one_chip.device_set]).reshape(1, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    prefill, *_ = jit_prefill(model, mesh, RuntimeConfig(), S_max, params_like, batch_like,
                              cache_like)
    decode, *_ = jit_decode_step(model, mesh, RuntimeConfig(), params_like, cache_like,
                                 tok_like)
    pre = prefill.lower(params_like, batch_like).compile()
    dec = decode.lower(params_like, cache_like, tok_like).compile()
    for compiled in (pre, dec):
        m = compiled.memory_analysis()
        total = (m.argument_size_in_bytes + m.output_size_in_bytes
                 + m.temp_size_in_bytes - m.alias_size_in_bytes)
        assert total < 15.75e9, total
    state_slice = B * cfg.n_ssm_heads * cfg.ssm_head_dim * cfg.ssm_state * 4   # 2.10 MB each
    assert dec.memory_analysis().temp_size_in_bytes < state_slice
    text = dec.as_text()
    for name in ("conv", "h", "k", "v"):
        leaf = cache_like[name]
        stack = {"float32": "f32", "bfloat16": "bf16"}[str(leaf.dtype)] + "[" \
            + ",".join(map(str, leaf.shape)) + "]"
        copies = re.findall(r"%(copy[\w.\-]*) = " + re.escape(stack), text)
        assert not copies, (name, copies)
