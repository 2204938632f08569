"""Device time by named program layer (``harness/scopes.py``), on the CPU.

* the bucket rule, ``op_name`` paths from compiled HLO text, and a reduction
  by hand: buckets, module executions, compile and host-event gap labels;
* the seven ``decode.*`` readers on a recorded v5e extract and without a
  trace;
* compilations found in a real (CPU) profile, and the serve loop's modules
  compiled again at a run's shapes with every layer's scope in their paths.
"""

import gzip
import json
import os

import jax
import jax.numpy as jnp
import pytest

import tiny
from harness import scopes, spec
from harness.core import Run
from harness.reference import Dims

PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11}
SEED = 2**31 + 4242
DECODE_METRICS = ("decode.device_ms", "decode.layer_scan_ms", "decode.kv_write_ms",
                  "decode.attend_ms", "decode.attn_proj_ms", "decode.mlp_ms",
                  "decode.lm_head_ms")
REST = ("embed", "norm", "unscoped")         # buckets with no metric of their own
D = "jit(decode_step)"


def _scoped_extract():
    """Two decode steps and a prefill in a 200 us window; one decode step
    starts after the window closes."""
    def op(name, start, dur, path, module="jit_decode_step"):
        return [name, float(start), float(dur), module, path]
    ops = [
        op("fusion.1", 1_000, 4_000, "jit(prefill)/layers/while/body/closed_call/mlp/dot",
           "jit_prefill"),
        op("fusion.81", 9_000, 1_000, f"{D}/embed/jit(_take)/gather"),
    ]
    for t in (10_000, 40_000):
        ops += [
            op("while.2", t, 18_000, f"{D}/layers/while"),
            op("dynamic-slice_fusion.10", t, 3_000, f"{D}/layers/while/body/dynamic_slice"),
            op("fusion.129", t + 3_000, 500, f"{D}/layers/while/body/closed_call/norm/rsqrt"),
            op("convert_fusion.4", t + 3_500, 1_500,
               f"{D}/layers/while/body/closed_call/attn_proj/dot_general"),
            op("dynamic_update_slice.22", t + 5_000, 1_000,
               f"{D}/layers/while/body/closed_call/kv_write/dynamic_update_slice"),
            op("fusion.133", t + 6_000, 2_000,
               f"{D}/layers/while/body/closed_call/attend/jit(_where)/select_n"),
            op("fusion.135", t + 8_000, 4_000, f"{D}/layers/while/body/closed_call/mlp/dot"),
            op("fusion.77", t + 18_000, 2_000, f"{D}/lm_head/dot_general"),
            op("copy.82", t + 20_000, 3_000, f"{D}/layers/while"),   # the carry, copied out
            op("copy.85", t + 23_000, 500, "cache['pos']"),
        ]
    ops.append(op("fusion.77", 250_000, 2_000, f"{D}/lm_head/dot_general"))
    modules = [["jit_prefill", 1_000.0, 4_000.0], ["jit_decode_step", 9_000.0, 15_000.0],
               ["jit_decode_step", 40_000.0, 24_000.0], ["jit_decode_step", 250_000.0, 2_000.0]]
    return {"devices": {"/device:TPU:0": ops}, "modules": {"/device:TPU:0": modules},
            "host": [["window", 0.0, 200_000.0]]}


def _run(trace_on=True):
    cell = tiny.serve_cell()
    return Run(cell=cell, dims=Dims.of(cell.config), peaks=PEAKS, trace_on=trace_on)


def _traced(ext):
    run = _run()
    run.data["scopes"] = scopes.reduce(ext)
    return run


def test_bucket_rule():
    assert scopes.bucket(f"{D}/layers/while/body/closed_call/attend/dot") == "attend"
    assert scopes.bucket(f"{D}/layers/while/body/squeeze") == "layer_scan"
    assert scopes.bucket(f"{D}/lm_head/dot_general") == "lm_head"
    assert scopes.bucket("cache['k']") == "unscoped"
    assert scopes.bucket("") == "unscoped"


def test_op_paths_from_hlo_text():
    text = "\n".join([
        "HloModule jit_decode_step, is_scheduled=true",
        "",
        "ENTRY %main.14 (p: s32[]) -> s32[] {",
        '  %cache__pos__.1 = s32[] parameter(0), metadata={op_name="cache[\'pos\']"}',
        "  %while.2 = (s32[], bf16[2]{0}) while(%tuple.38), condition=%c, body=%b",
        "  %get-tuple-element.443 = bf16[2]{0} get-tuple-element(%while.2), index=1, "
        'metadata={op_name="jit(decode_step)/layers/while" stack_frame_id=12}',
        '  %copy.82 = bf16[2]{0} copy(%get-tuple-element.443), backend_config={"a":[]}',
        "  %copy.85 = s32[]{:T(128)} copy(%cache__pos__.1)",
        "  ROOT %tuple.41 = (bf16[2]{0}, s32[]) tuple(%copy.82, %copy.85)",
        "}"])
    module, paths = scopes.op_paths(text)
    assert module == "jit_decode_step"
    assert paths["copy.82"] == "jit(decode_step)/layers/while"
    assert scopes.bucket(paths["copy.82"]) == "layer_scan"
    assert scopes.bucket(paths["copy.85"]) == "unscoped"
    assert paths["while.2"] == ""
    ext = {"devices": {"/device:TPU:0": [["copy.82", 0.0, 1.0, "jit_decode_step", ""],
                                         ["copy.82", 2.0, 1.0, "jit_prefill", ""]]}}
    scopes.assign_paths(ext, [text])
    assert [row[4] for row in ext["devices"]["/device:TPU:0"]] == [
        "jit(decode_step)/layers/while", ""]


def test_trace_buckets_by_hand():
    run = _traced(_scoped_extract())
    r = run.data["scopes"]
    assert r["modules"] == {"jit_prefill": 1, "jit_decode_step": 2}
    step = {"embed": 0.5e-6, "layer_scan": 6e-6, "norm": 0.5e-6, "attn_proj": 1.5e-6,
            "kv_write": 1e-6, "attend": 2e-6, "mlp": 4e-6, "lm_head": 2e-6, "unscoped": 0.5e-6}
    assert r["buckets"]["jit_decode_step"] == pytest.approx({k: 2 * v for k, v in step.items()})
    assert r["buckets"]["jit_prefill"] == pytest.approx({"mlp": 4e-6})
    ops = dict(r["device_ops"][:3])
    assert ops["jit_decode_step/layer_scan:dynamic-slice_fusion.10"] == pytest.approx(6e-6)
    assert ops["jit_decode_step/layer_scan:copy.82"] == pytest.approx(6e-6)
    read = {m: spec.reader(m)(run) for m in DECODE_METRICS}
    assert read == pytest.approx({
        "decode.device_ms": 1e3 * sum(step.values()), "decode.layer_scan_ms": 6e-3,
        "decode.kv_write_ms": 1e-3, "decode.attend_ms": 2e-3, "decode.attn_proj_ms": 1.5e-3,
        "decode.mlp_ms": 4e-3, "decode.lm_head_ms": 2e-3})
    # the buckets add up to the step's device time, the rest printed beside
    rest = sum(scopes.per_step_ms(run, "jit_decode_step", b) for b in REST)
    assert sum(read[m] for m in DECODE_METRICS[1:]) + rest == pytest.approx(
        read["decode.device_ms"], rel=1e-12)
    assert r["compiles_in_window"] == 0 and r["compile_s_in_window"] == 0


def test_gap_labels_by_hand():
    """Two 160 us gaps' worth of idle: one under a compilation, one where the
    host sat in a call inside its token wait."""
    def ext(compiles):
        return {"devices": {"/device:TPU:0": [["a", 0.0, 20_000.0, "jit_decode_step", ""],
                                             ["a", 180_000.0, 20_000.0, "jit_decode_step", ""],
                                             ["a", 200_000.0, 5.0, "jit_decode_step", ""],
                                             ["a", 200_010.0, 80_000.0, "jit_decode_step", ""]]},
                "host": [["window", 0.0, 300_000.0], ["decode_step", 20_000.0, 160_000.0],
                         ["dispatch", 20_000.0, 50_000.0], ["token_wait", 80_000.0, 100_000.0],
                         ["$numpy asarray", 90_000.0, 20_000.0], ["gc", 95_000.0, 1_000.0]],
                "compiles": compiles}
    gaps = dict(scopes.reduce(ext([]))["idle_gaps"])
    # the gap's middle (100 us) lies in asarray, not in the gc call that ended
    # before it; the 5 ns gap is between ops; after the last op, no span
    assert gaps == pytest.approx({"$numpy asarray": 160e-6, "between_ops": 5e-9,
                                  "none": 19_990e-9})
    r = scopes.reduce(ext([[95_000.0, 10_000.0], [400_000.0, 1.0]]))
    assert dict(r["idle_gaps"]) == pytest.approx({"compile": 160e-6, "between_ops": 5e-9,
                                                  "none": 19_990e-9})
    # a compilation that overlaps the window counts, clipped to it
    assert r["compiles_in_window"] == 1 and r["compile_s_in_window"] == pytest.approx(10e-6)


def test_decode_readers_on_a_recorded_scoped_trace():
    """Eight decode steps of phi4-chat traced on a TPU v5 lite."""
    path = os.path.join(os.path.dirname(__file__), "data", "trace_extract_scoped.json.gz")
    with gzip.open(path, "rt") as f:
        ext = json.load(f)
    run = _traced(ext)
    assert run.data["scopes"]["modules"]["jit_decode_step"] == 8
    read = {m: spec.reader(m)(run) for m in DECODE_METRICS}
    assert all(v > 0 for v in read.values()), read
    by = run.data["scopes"]["buckets"]["jit_decode_step"]
    assert set(by) <= {"layer_scan", "kv_write", "attend", "attn_proj", "mlp", "lm_head",
                       *REST}
    rest = sum(scopes.per_step_ms(run, "jit_decode_step", b) for b in REST)
    assert sum(read[m] for m in DECODE_METRICS[1:]) + rest == pytest.approx(
        read["decode.device_ms"], rel=1e-9)
    assert rest < 0.1 * read["decode.device_ms"]


@pytest.mark.parametrize("name", DECODE_METRICS)
def test_decode_readers_without_a_trace(name):
    assert spec.reader(name)(_run(trace_on=False)) is None
    assert spec.reader(name)(_run(trace_on=True)) is None     # no profile was taken
    # a traced program whose modules have other names (no jit_decode_step)
    ext = _scoped_extract()
    ext["modules"] = {p: [["jit_hinted", *m[1:]] for m in ms] for p, ms in ext["modules"].items()}
    assert spec.reader(name)(_traced(ext)) is None


def test_compiles_found_in_a_cpu_profile(tmp_path):
    from jax.profiler import TraceAnnotation
    jax.jit(lambda x: x - 1)(jnp.ones(5)).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with TraceAnnotation("cb:window"):
        jax.jit(lambda x: x * 3 + 1)(jnp.ones(7)).block_until_ready()
    jax.profiler.stop_trace()
    ext = scopes.extract(str(tmp_path))
    assert ext["devices"] == {}                  # the CPU's ops are not a TPU's
    assert "window" in [n for n, _, _ in ext["host"]]
    r = scopes.reduce(ext)
    assert r["compiles_in_window"] >= 1 and r["compile_s_in_window"] > 0


def test_serve_modules_compiled_again_carry_every_scope():
    """The loop's two modules, compiled again at a run's shapes as the readers
    do after the window, with the scopes of the smoke config's layers."""
    run = _run(trace_on=False)
    from repro.launch.serve import cache_len
    run.data.update(B=3, P=16, S_max=cache_len(16 + 16))
    texts = scopes.compiled_texts(run)
    found = dict(scopes.op_paths(t) for t in texts)
    assert set(found) == {"jit_prefill", "jit_decode_step"}
    every = {"embed", "layer_scan", "norm", "attn_proj", "attend", "mlp", "lm_head"}
    layers = {m: {scopes.bucket(p) for p in paths.values()} for m, paths in found.items()}
    assert every <= layers["jit_prefill"]          # prefill builds the cache whole
    assert every | {"kv_write"} <= layers["jit_decode_step"]
