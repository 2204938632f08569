"""The hybrid serving loop (``harness/serve_hybrid.py``) and its readers, on
the CPU at the smoke size of granite-4.0-h.

* operations counted from shapes equal ``repro.runtime.costs.jaxpr_costs``;
* a whole run, with the chip check skipped, is correct; with the decode
  step's SSM state corrupted, or a multiplier dropped from it, or with the
  fp8 control in the program's place, it is not;
* the weights keep the per-head scalars in float32 within their ranges;
* ``ssm_scopes`` buckets an extract by hand, and its readers read nothing
  without a trace;
* the serve modules compiled again carry the mixer's scopes.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import run as runpy
import tiny_hybrid
from harness import costs_hybrid, scopes, spec, ssm_scopes, weights_hybrid
from harness.core import Run
from harness.reference import Dims
from harness.reference_hybrid import HDims
from harness.serve_hybrid import hybrid_config

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2**31 + 4242
NEW_METRICS = ("decode.ssm_state_ms", "decode.ssm_proj_ms", "prefill.ssm_scan_ms",
               "ssm_state_roofline", "ssd_scan_roofline")


def _program():
    from repro.models import build_model
    return build_model(hybrid_config(tiny_hybrid.GRANITE_H))


@pytest.mark.parametrize("B,S,S_max", [(2, 64, 128), (3, 45, 96)])
def test_flops_match_jaxpr_costs(B, S, S_max):
    """At a prompt the chunk divides and one it does not."""
    from repro.runtime.costs import jaxpr_costs
    model, dims = _program(), HDims.of(tiny_hybrid.GRANITE_H)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    toks = jax.ShapeDtypeStruct((B, S), jnp.int32)
    jp = jax.make_jaxpr(lambda p, t: model.prefill(p, {"tokens": t}, S_max))(params, toks)
    assert jaxpr_costs(jp)["flops"] == costs_hybrid.prefill_flops(dims, B, S, executed=True)
    cache = jax.eval_shape(lambda p, t: model.prefill(p, {"tokens": t}, S_max), params, toks)[1]
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    jd = jax.make_jaxpr(lambda p, c, t: model.decode_step(p, c, {"token": t}))(params, cache, tok)
    assert jaxpr_costs(jd)["flops"] == costs_hybrid.decode_flops(dims, B, S, S_max, executed=True)
    assert costs_hybrid.prefill_flops(dims, B, S) < costs_hybrid.prefill_flops(dims, B, S, True)
    assert costs_hybrid.decode_flops(dims, B, S, S_max) < costs_hybrid.decode_flops(
        dims, B, S, S_max, True)


def test_head_scalars_in_their_ranges():
    model = _program()
    like = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p = weights_hybrid.make_params(jax.random.PRNGKey(3), like, 512, "float32")
    m = p["mamba_layers"]["mamba"]
    A = -np.exp(np.asarray(m["A_log"]))
    dt = np.log1p(np.exp(np.asarray(m["dt_bias"])))
    assert m["A_log"].dtype == m["dt_bias"].dtype == m["D"].dtype == jnp.float32
    assert -16.1 < A.min() and A.max() < -0.99 and A.std() > 1.0
    assert 0.99e-3 < dt.min() and dt.max() < 0.101
    assert np.all(np.asarray(m["D"]) == 1) and not np.any(np.asarray(m["conv_b"]))
    # bfloat16 values held in float32
    a = np.asarray(m["A_log"])
    assert np.array_equal(a, np.asarray(jnp.asarray(a).astype(jnp.bfloat16).astype(jnp.float32)))
    with pytest.raises(ValueError):
        weights_hybrid.make_params(jax.random.PRNGKey(3), like, 512, "bfloat16")


# -- whole runs on the CPU: sound, and with the decode step broken -------------
def _serve(seconds=1.0):
    from harness.serve_hybrid import drive
    cell = tiny_hybrid.serve_cell()
    run = Run(cell=cell, dims=Dims.of(cell.config), peaks=PEAKS, trace_on=False)
    return drive(run, SEED, seconds), run


def _wrap_decode(monkeypatch, wrap):
    import repro.runtime as rtm
    orig = rtm.jit_decode_step
    monkeypatch.setattr(rtm, "jit_decode_step", lambda model, *a, **kw: wrap(orig, model, *a, **kw))


def test_serve_hybrid_run_is_correct():
    res, run = _serve()
    assert runpy.judge(res["checks"]), res["checks"]
    assert res["checks"]["logit_gap"][0] < 1e-4
    assert res["attempted"] >= run.counters["requests"] >= run.counters["finished"] > 0
    for name in ("prefill_ms", "decode_step_ms", "mfu.hybrid_prefill", "mfu.hybrid_decode"):
        assert spec.reader(name)(run) > 0
    for name in NEW_METRICS:
        assert spec.reader(name)(run) is None                 # no trace, no reading


def test_serve_hybrid_fault_state_corrupted(monkeypatch):
    """A decode step that hands back every SSM state halved."""
    def wrap(orig, model, *a, **kw):
        step, *rest = orig(model, *a, **kw)

        def broken(p, c, b):
            logits, c = step(p, c, b)
            return logits, dict(c, h=c["h"] * 0.5)
        return (broken, *rest)
    _wrap_decode(monkeypatch, wrap)
    res, _ = _serve()
    assert not runpy.judge(res["checks"])


def test_serve_hybrid_fault_multiplier_dropped(monkeypatch):
    """A decode step built without the residual multiplier."""
    from repro.models import build_model

    def wrap(orig, model, *a, **kw):
        return orig(build_model(dataclasses.replace(model.cfg, residual_multiplier=1.0)),
                    *a, **kw)
    _wrap_decode(monkeypatch, wrap)
    res, _ = _serve()
    assert not runpy.judge(res["checks"])


def test_serve_hybrid_control_fails():
    """The fp8 reference in the program's place, read on the inputs of a
    sound run's check, is judged not correct by the same comparison."""
    res, run = _serve()
    assert runpy.judge(res["checks"]), res["checks"]
    ctl = runpy.loop(run.cell).control(run, SEED)
    assert not runpy.judge(ctl), ctl


# -- the mixer's buckets ------------------------------------------------------------
D = "jit(decode_step)/layers/while/body"


def _extract():
    def op(name, start, dur, path, module="jit_decode_step"):
        return [name, float(start), float(dur), module, path]
    ops = []
    for t in (10_000, 40_000):
        ops += [
            op("while.1", t, 12_000, f"{D}"),
            op("fusion.1", t, 1_000, f"{D}/dynamic_slice"),
            op("fusion.2", t + 1_000, 2_000, f"{D}/closed_call/ssm_proj/dot_general"),
            op("fusion.3", t + 3_000, 500, f"{D}/closed_call/ssm_conv/mul"),
            op("fusion.4", t + 3_500, 4_000, f"{D}/closed_call/ssm_state/dynamic_update_slice"),
            op("fusion.5", t + 7_500, 2_500, f"{D}/closed_call/mlp/dot_general"),
            op("fusion.6", t + 10_000, 2_000, "jit(decode_step)/lm_head/dot_general"),
        ]
    ops.append(op("fusion.7", 100_000, 8_000,
                  "jit(prefill)/layers/while/body/ssm_scan/dot_general", "jit_prefill"))
    modules = [["jit_decode_step", 10_000.0, 12_000.0], ["jit_decode_step", 40_000.0, 12_000.0],
               ["jit_prefill", 100_000.0, 8_000.0]]
    return {"devices": {"/device:TPU:0": ops}, "modules": {"/device:TPU:0": modules},
            "host": [["window", 0.0, 200_000.0]]}


def _traced(ext):
    cell = tiny_hybrid.serve_cell()
    run = Run(cell=cell, dims=Dims.of(cell.config), peaks=PEAKS, trace_on=True)
    run.data.update(B=2, P=64, ssm_scopes=ssm_scopes.reduce(ext))
    return run


def test_ssm_buckets_by_hand():
    run = _traced(_extract())
    read = {m: spec.reader(m)(run) for m in NEW_METRICS}
    assert read["decode.ssm_proj_ms"] == pytest.approx(2e-3)
    assert read["decode.ssm_state_ms"] == pytest.approx(4e-3)
    assert read["prefill.ssm_scan_ms"] == pytest.approx(8e-3)
    dims = HDims.of(tiny_hybrid.GRANITE_H)
    bytes_ = costs_hybrid.state_step_bytes(dims, 2)
    assert read["ssm_state_roofline"] == pytest.approx(100 * bytes_ / 1e11 / 4e-6)
    assert 0 < read["ssd_scan_roofline"]
    # under scopes.VOCAB alone the mixer's ops fall into layer_scan
    assert scopes.bucket(f"{D}/closed_call/ssm_state/x") == "layer_scan"
    assert ssm_scopes.bucket(f"{D}/closed_call/ssm_state/x") == "ssm_state"


@pytest.mark.parametrize("name", NEW_METRICS)
def test_ssm_readers_without_a_trace(name):
    cell = tiny_hybrid.serve_cell()
    for trace_on in (False, True):
        run = Run(cell=cell, dims=Dims.of(cell.config), peaks=PEAKS, trace_on=trace_on)
        assert spec.reader(name)(run) is None
    # a program whose steps carry none of the mixer's names (the dense model)
    ext = _extract()
    for ops in ext["devices"].values():
        for row in ops:
            row[4] = row[4].replace("ssm_", "x_")
    assert spec.reader(name)(_traced(ext)) is None


def test_serve_modules_compiled_again_carry_the_mixer_scopes():
    cell = tiny_hybrid.serve_cell()
    run = Run(cell=cell, dims=Dims.of(cell.config), peaks=PEAKS, trace_on=False)
    run.data.update(B=3, P=40, S_max=56)
    found = dict(scopes.op_paths(t) for t in scopes.compiled_texts(run))
    got = {m: {ssm_scopes.bucket(p) for p in paths.values()} for m, paths in found.items()}
    assert {"ssm_proj", "ssm_conv", "ssm_scan", "attend", "mlp", "lm_head"} <= got["jit_prefill"]
    assert {"ssm_proj", "ssm_conv", "ssm_state", "kv_write", "attend", "mlp",
            "lm_head"} <= got["jit_decode_step"]
