"""The benchmark's own checks, on the CPU at tiny sizes.

* every configuration, mix, limit and metric of ``BENCHMARK.json`` is found
  by name, and an unknown device or a missing program is an error;
* operations counted from shapes equal ``repro.runtime.costs.jaxpr_costs``;
* the trace reduction on a recorded extract;
* a whole run, with the chip check skipped, is correct, and comes out not
  correct with the timed path broken underneath (one test per fault) and
  with the lower-precision control in the program's place.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

import run as runpy
import tiny
from harness import costs, spec, trace
from harness.core import Run
from harness.reference import Dims

ROOT = os.path.dirname(spec.BENCH_DIR)
PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2**31 + 4242


def test_every_name_is_found():
    bench = spec.benchmark()
    for w in bench["workloads"]:
        cell = spec.find_cell(w["name"], bench)
        assert callable(runpy.loop(cell).drive) and callable(runpy.loop(cell).control)
        assert cell.end_to_end and cell.per_layer
        Dims.of(cell.config)
        for m in cell.per_layer:
            assert callable(spec.reader(m["name"]))
    names = {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert len(names) == len(bench["end_to_end"]) + len(bench["per_layer"])
    assert spec.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12


def test_unknown_device_is_an_error():
    with pytest.raises(SystemExit):
        spec.peaks("TPU v99")
    with pytest.raises(SystemExit):
        spec.find_cell("no-such-cell")


def _run_cli(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    p = subprocess.run([sys.executable, "chipbench/run.py", "--workload", "phi4-chat",
                        "--seed", "1", "--seconds", "1", "--trace", "0"],
                       cwd=cwd, env=env, capture_output=True, text=True, timeout=300)
    return p.returncode, p.stdout


def test_no_tpu_means_no_result():
    rc, out = _run_cli(ROOT)
    assert rc != 0
    assert "{" not in out


def test_without_the_program_there_is_no_result(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(spec.BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {"PYTHONPATH": ""}
    rc, out = _run_cli(tmp_path, env)
    assert rc != 0
    assert "{" not in out


# -- operations from shapes against the program's own jaxpr counter -----------
def _program(conf):
    from harness.core import program_config
    from repro.models import build_model
    cfg = program_config(conf)
    return build_model(cfg), cfg


def test_flops_match_jaxpr_costs():
    from repro.runtime.costs import jaxpr_costs
    conf = tiny.DENSE
    model, cfg = _program(conf)
    dims = Dims.of(conf)
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    B, S, S_max = 2, 64, 128
    toks = jax.ShapeDtypeStruct((B, S), jnp.int32)
    jp = jax.make_jaxpr(lambda p, t: model.prefill(p, {"tokens": t}, S_max))(params, toks)
    assert jaxpr_costs(jp)["flops"] == costs.prefill_flops(dims, B, S, executed=True)
    cache = jax.eval_shape(lambda p, t: model.prefill(p, {"tokens": t}, S_max), params, toks)[1]
    tok = jax.ShapeDtypeStruct((B,), jnp.int32)
    jd = jax.make_jaxpr(lambda p, c, t: model.decode_step(p, c, {"token": t}))(params, cache, tok)
    assert jaxpr_costs(jd)["flops"] == costs.decode_flops(dims, B, S, S_max, executed=True)
    # what the algorithm needs is less than what the jnp path computes
    assert costs.prefill_flops(dims, B, S) < costs.prefill_flops(dims, B, S, executed=True)


# -- the trace reduction --------------------------------------------------------
def test_trace_reduction_by_hand():
    ext = {"devices": {"/device:TPU:0": [["a", 100.0, 50.0], ["b", 120.0, 60.0],
                                         ["a", 300.0, 100_000.0]]},
           "host": [["window", 0.0, 200_000.0], ["decode_step", 0.0, 250.0],
                    ["pick", 180.0, 120.0], ["prefill", 150_000.0, 40_000.0]]}
    r = trace.reduce(ext)
    assert r["window_s"] == pytest.approx(200e-6)
    assert r["busy_s"] == pytest.approx((80 + 100_000) * 1e-9)
    ops = dict(r["device_ops"])
    assert ops == pytest.approx({"a": 100_050e-9, "b": 60e-9})
    gaps = dict(r["idle_gaps"])
    # a gap goes to the innermost span at its middle; short ones are between ops
    assert gaps == pytest.approx({"prefill": 99_700e-9, "between_ops": 220e-9})


def test_trace_reduction_on_a_recorded_trace():
    with open(os.path.join(os.path.dirname(__file__), "data", "trace_extract.json")) as f:
        ext = json.load(f)
    r = trace.reduce(ext)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert r["device_ops"] and r["idle_gaps"]
    total_idle = sum(v for _, v in r["idle_gaps"])
    assert total_idle <= r["window_s"] - r["busy_s"] + 1e-9


# -- whole runs on the CPU: sound, and with the timed path broken ----------------
def _serve(cell=None, seconds=1.0):
    from harness.serve import drive
    cell = cell or tiny.serve_cell()
    run = Run(cell=cell, dims=Dims.of(cell.config), peaks=PEAKS, trace_on=False)
    res = drive(run, SEED, seconds)
    return res, run


def test_serve_run_is_correct():
    res, run = _serve()
    assert runpy.judge(res["checks"]), res["checks"]
    assert res["attempted"] >= run.counters["requests"] >= run.counters["finished"] > 0
    for name in ("prefill_ms", "decode_step_ms", "mfu.prefill", "mfu.decode",
                 "prefill_step_roofline", "decode_step_roofline"):
        assert spec.reader(name)(run) > 0
    assert spec.reader("device_idle.serve")(run) is None     # no trace, no reading


def _wrap_decode(monkeypatch, change):
    import repro.runtime as rtm
    orig = rtm.jit_decode_step

    def wrapped(*a, **kw):
        step, *rest = orig(*a, **kw)
        return (lambda p, c, b: change(step, p, c, b), *rest)
    monkeypatch.setattr(rtm, "jit_decode_step", wrapped)


def test_serve_fault_state_unchanged(monkeypatch):
    """A decode step that returns its cache unchanged."""
    def change(step, p, c, b):
        logits, _ = step(p, jax.tree.map(jnp.copy, c), b)
        return logits, c
    _wrap_decode(monkeypatch, change)
    res, _ = _serve()
    assert not runpy.judge(res["checks"])


def test_serve_fault_token_altered(monkeypatch):
    """Logits altered where they are produced, so every slot's token differs."""
    def change(step, p, c, b):
        logits, c = step(p, c, b)
        rows = jnp.arange(logits.shape[0])
        return logits.at[rows, (jnp.argmax(logits, -1) + 1) % logits.shape[-1]].add(1e3), c
    _wrap_decode(monkeypatch, change)
    res, _ = _serve()
    assert not runpy.judge(res["checks"])


# -- the control: the reference one precision down in the program's place -------
def test_serve_control_fails():
    """The fp8 reference in the program's place, read on the inputs of a sound
    run's check, is judged not correct by the same comparison."""
    res, run = _serve()
    assert runpy.judge(res["checks"]), res["checks"]
    ctl = runpy.loop(run.cell).control(run, SEED)
    assert not runpy.judge(ctl), ctl
