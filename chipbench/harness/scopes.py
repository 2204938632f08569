"""Device time by the program's named layers, compilations in the window, and
what the host was doing while the device sat idle, from a traced run's
profile.

The program names its work with ``jax.named_scope`` from one vocabulary
(``VOCAB``; ``repro.models.transformer``), and its jitted serve steps are the
modules ``jit_prefill`` and ``jit_decode_step``. ``extract`` reads the profile
``trace.py`` reads, again, and keeps for each device op its module (the
interval of the device's ``XLA Modules`` line that holds it). A v5e trace's
op events carry no ``op_name``, so ``assign_paths`` takes each op's path from
the compiled HLO text of its module (``compiled_texts``, compiled again from
the run's compile cache after the window); an op the compiler added without
one takes its first operand's. ``reduce`` works on the extract alone, so the
CPU self-tests check it on a recorded extract.

The bucket rule (``bucket``): the innermost vocabulary name in an op's path;
``layer_scan`` for an op under ``layers`` with no inner name; ``unscoped`` for
an op under no name.

Compilations are the ``backend_compile*`` annotations JAX writes on the host
plane. An idle gap of ``SHORT_NS`` or more is labelled ``compile`` where a
compilation covers its middle, else by the innermost event of the window's
host thread there: a benchmark span, a call the Python tracer recorded, or a
runtime annotation.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict

from .trace import CONTAINERS, PREFIX, SHORT_NS, _union

VOCAB = ("embed", "layers", "norm", "attn_proj", "kv_write", "attend", "mlp", "moe",
         "lm_head")
SERVE_MODULES = ("jit_prefill", "jit_decode_step")
COMPILE_EVENTS = ("backend_compile", "backend_compile_and_load")
# where the device's buckets are printed beside the counters, in ms a step
SHOWN = (("decode", "jit_decode_step"), ("prefill", "jit_prefill"))


def bucket(path: str) -> str:
    """The program layer an ``op_name`` path puts an op in."""
    for part in reversed(path.split("/")):
        if part in VOCAB:
            return "layer_scan" if part == "layers" else part
    return "unscoped"


def op_paths(hlo_text: str) -> tuple[str, dict]:
    """(module, {instruction: op_name path}) from a compiled module's HLO text."""
    module = hlo_text.split(None, 2)[1].rstrip(",")
    paths = {}
    for line in hlo_text.splitlines():
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*)", line)
        if not m:
            continue
        name, rest = m.groups()
        own = re.search(r'op_name="([^"]*)"', rest)
        if own:
            paths[name] = own.group(1)
        else:
            operands = re.findall(r"%([\w.\-]+)", rest.split(", metadata=", 1)[0])
            paths[name] = next((paths[o] for o in operands if paths.get(o)), "")
    return module, paths


def extract(trace_dir: str) -> dict:
    """{"devices": {plane: [[op, start_ns, dur_ns, module, ""], ...]},
        "modules": {plane: [[module, start_ns, dur_ns], ...]},
        "host": [[event, start_ns, dur_ns], ...] of the thread that holds the
                window span, benchmark spans without their prefix,
        "compiles": [[start_ns, dur_ns], ...]} from the newest trace."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices, modules, host, compiles = {}, {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            if "XLA Ops" not in lines:
                continue
            mods = sorted(([e.name.split("(", 1)[0], float(e.start_ns), float(e.duration_ns)]
                           for e in (lines["XLA Modules"].events
                                     if "XLA Modules" in lines else ())),
                          key=lambda m: m[1])
            modules[plane.name] = mods
            devices[plane.name] = [[e.name.split(" = ", 1)[0].lstrip("%")[:80],
                                    float(e.start_ns), float(e.duration_ns), "?", ""]
                                   for e in lines["XLA Ops"].events]
            _put_modules(devices[plane.name], mods)
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                events = [[e.name, float(e.start_ns), float(e.duration_ns)] for e in ln.events]
                compiles += [[s, d] for n, s, d in events if n in COMPILE_EVENTS]
                if any(n == PREFIX + "window" for n, _, _ in events):
                    host = [[n[len(PREFIX):] if n.startswith(PREFIX) else n[:80], s, d]
                            for n, s, d in events]
    return {"devices": devices, "modules": modules, "host": host, "compiles": compiles}


def _put_modules(ops, mods):
    """Each op's module: the module execution that holds its start."""
    starts = [s for _, s, _ in mods]
    for row in ops:
        i = bisect.bisect_right(starts, row[1]) - 1
        if i >= 0 and row[1] <= mods[i][1] + mods[i][2]:
            row[3] = mods[i][0]


def assign_paths(ext: dict, hlo_texts) -> None:
    """Fills each op's ``op_name`` path from the compiled HLO texts."""
    paths = dict(op_paths(t) for t in hlo_texts)
    for ops in ext["devices"].values():
        for row in ops:
            row[4] = paths.get(row[3], {}).get(row[0], "")


def compiled_texts(run) -> list[str]:
    """The compiled HLO text of the serve loop's ``jit_prefill`` and
    ``jit_decode_step``, built at the run's shapes as ``serve.py`` builds
    them; the executables come from the run's compile cache."""
    import jax
    import jax.numpy as jnp
    from repro.launch.mesh import make_host_mesh
    from repro.models import build_model
    from repro.runtime import RuntimeConfig, jit_decode_step, jit_prefill

    from .core import program_config
    from .weights import seed_key

    B, P, S_max = run.data["B"], run.data["P"], run.data["S_max"]
    model = build_model(program_config(run.cell.config))
    rt = RuntimeConfig()
    mesh = make_host_mesh(1, 1)
    params_like = jax.eval_shape(model.init, seed_key(0))
    batch_like = {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)}
    cache_like = jax.eval_shape(
        lambda p, b: model.prefill(p, b, S_max), params_like, batch_like)[1]
    tok_like = {"token": jax.ShapeDtypeStruct((B,), jnp.int32)}
    prefill = jit_prefill(model, mesh, rt, S_max, params_like, batch_like, cache_like)[0]
    decode = jit_decode_step(model, mesh, rt, params_like, cache_like, tok_like)[0]
    return [prefill.lower(params_like, batch_like).compile().as_text(),
            decode.lower(params_like, cache_like, tok_like).compile().as_text()]


def _innermost(events, times):
    """For each time (ascending), the innermost of the nested ``events``
    ([name, start, end], by start, outer first) that holds it, or "none"."""
    out, stack, i = [], [], 0
    for t in times:
        while i < len(events) and events[i][1] <= t:
            while stack and stack[-1][2] < events[i][1]:
                stack.pop()
            stack.append(events[i])
            i += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        out.append(stack[-1][0] if stack else "none")
    return out


def reduce(ext: dict, top: int = 10) -> dict:
    """Over the window, averaged over the chips: per module its executions
    that start in the window and its device seconds by bucket; the longest
    ops as ``<module>/<bucket>:<op>``; idle gaps by label; and the
    compilations that overlap the window, clipped to it."""
    host = sorted(((n, s, s + d) for n, s, d in ext["host"]), key=lambda h: (h[1], -h[2]))
    win = [h for h in host if h[0] == "window"]
    if not win:
        raise RuntimeError("trace holds no window span")
    w0, w1 = win[0][1], win[0][2]
    compiles = [(max(s, w0), min(s + d, w1)) for s, d in ext.get("compiles", ())
                if s + d > w0 and s < w1]
    op_time = defaultdict(float)
    buckets = defaultdict(lambda: defaultdict(float))
    gaps = defaultdict(float)
    for ops in ext["devices"].values():
        iv = []
        for name, s, d, module, path in ops:
            s2, e2 = max(s, w0), min(s + d, w1)
            if e2 > s2:
                iv.append((s2, e2))
                if not name.startswith(CONTAINERS):
                    layer = bucket(path)
                    op_time[f"{module}/{layer}:{name}"] += (e2 - s2) * 1e-9
                    buckets[module][layer] += (e2 - s2) * 1e-9
        u = _union(iv)
        edges = [w0] + [x for se in u for x in se] + [w1]
        long_gaps = []
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= SHORT_NS:
                long_gaps.append((a, b))
            elif b > a:
                gaps["between_ops"] += (b - a) * 1e-9
        mids = [(a + b) / 2 for a, b in long_gaps]
        for (a, b), t, label in zip(long_gaps, mids, _innermost(host, mids)):
            if any(c0 <= t <= c1 for c0, c1 in compiles):
                label = "compile"
            elif label == "window":
                label = "none"
            gaps[label] += (b - a) * 1e-9
    runs = defaultdict(int)
    for mods in ext.get("modules", {}).values():
        for name, s, _ in mods:
            if w0 <= s < w1:
                runs[name] += 1
    n = max(len(ext["devices"]), 1)
    return {
        "modules": {k: v / n for k, v in runs.items()},
        "buckets": {m: {b: v / n for b, v in bs.items()} for m, bs in buckets.items()},
        "device_ops": sorted(([k, v / n] for k, v in op_time.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v / n] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
        "compiles_in_window": len(compiles),
        "compile_s_in_window": sum(b - a for a, b in compiles) * 1e-9,
    }


def layers(run) -> dict | None:
    """``reduce`` of the run's profile, made once per run and kept in
    ``run.data["scopes"]``, its numbers also put among the run's counters;
    None in an untraced run."""
    if "scopes" not in run.data:
        run.data["scopes"] = _layers(run)
    return run.data["scopes"]


def _layers(run):
    trace_dir = run.data.get("trace_dir")
    if not run.trace_on or not trace_dir:
        return None
    ext = extract(trace_dir)
    names = {m for mods in ext["modules"].values() for m, _, _ in mods}
    if names & set(SERVE_MODULES) and {"B", "P", "S_max"} <= set(run.data):
        assign_paths(ext, compiled_texts(run))
    r = reduce(ext)
    run.counters.update(
        compiles_in_window=r["compiles_in_window"],
        compile_s_in_window=r["compile_s_in_window"],
        **{f"{step}_buckets_ms": {b: 1e3 * v / r["modules"][module]
                                  for b, v in r["buckets"].get(module, {}).items()}
           for step, module in SHOWN if r["modules"].get(module)},
        scoped_device_ops=r["device_ops"],
        idle_gaps_by_host_event=r["idle_gaps"])
    return r


def per_step_ms(run, module: str, which: str | None = None):
    """Device ms per execution of ``module`` in the traced window, in bucket
    ``which`` (all buckets where None); None without a trace or executions."""
    r = layers(run)
    if not r or not r["modules"].get(module):
        return None
    by = r["buckets"].get(module, {})
    secs = sum(by.values()) if which is None else by.get(which, 0.0)
    return 1e3 * secs / r["modules"][module]
