"""Device time of a Mamba2 hybrid's serve steps by named layer, with the
mixer's own names beside ``scopes.VOCAB``.

The program names the mixer's work ``ssm_proj`` (in/out projections and the
gated norm), ``ssm_conv`` (the causal conv and its window), ``ssm_scan``
(the prefill's chunked SSD) and ``ssm_state`` (the decode step's state
update). Under ``scopes.VOCAB`` alone those ops fall into ``layer_scan``;
here each op goes to the innermost name of ``VOCAB``, by ``scopes``' rule.
The profile is read with ``scopes.extract``, ``scopes.assign_paths`` and
``scopes.compiled_texts``, once per run, and the result kept in
``run.data["ssm_scopes"]``; its buckets of both steps are also put among the
run's counters (``decode_ssm_buckets_ms``, ``prefill_ssm_buckets_ms``).
"""

from __future__ import annotations

from collections import defaultdict

from . import scopes
from .trace import CONTAINERS

SSM = ("ssm_proj", "ssm_conv", "ssm_scan", "ssm_state")
VOCAB = scopes.VOCAB + SSM


def bucket(path: str) -> str:
    """``scopes.bucket`` over ``VOCAB``."""
    for part in reversed(path.split("/")):
        if part in VOCAB:
            return "layer_scan" if part == "layers" else part
    return "unscoped"


def reduce(ext: dict) -> dict:
    """Over the window, averaged over the chips: per module its executions
    that start in the window and its device seconds by bucket."""
    win = [(s, s + d) for n, s, d in ext["host"] if n == "window"]
    if not win:
        raise RuntimeError("trace holds no window span")
    w0, w1 = win[0]
    buckets = defaultdict(lambda: defaultdict(float))
    for ops in ext["devices"].values():
        for name, s, d, module, path in ops:
            s2, e2 = max(s, w0), min(s + d, w1)
            if e2 > s2 and not name.startswith(CONTAINERS):
                buckets[module][bucket(path)] += (e2 - s2) * 1e-9
    runs = defaultdict(int)
    for mods in ext.get("modules", {}).values():
        for name, s, _ in mods:
            if w0 <= s < w1:
                runs[name] += 1
    n = max(len(ext["devices"]), 1)
    return {"modules": {k: v / n for k, v in runs.items()},
            "buckets": {m: {b: v / n for b, v in bs.items()} for m, bs in buckets.items()}}


def layers(run) -> dict | None:
    """``reduce`` of the run's profile, made once per run; None in an
    untraced run."""
    if "ssm_scopes" not in run.data:
        run.data["ssm_scopes"] = _layers(run)
    return run.data["ssm_scopes"]


def _layers(run):
    trace_dir = run.data.get("trace_dir")
    if not run.trace_on or not trace_dir:
        return None
    ext = scopes.extract(trace_dir)
    names = {m for mods in ext["modules"].values() for m, _, _ in mods}
    if names & set(scopes.SERVE_MODULES) and {"B", "P", "S_max"} <= set(run.data):
        scopes.assign_paths(ext, scopes.compiled_texts(run))
    r = reduce(ext)
    run.counters.update(
        {f"{step}_ssm_buckets_ms": {b: 1e3 * v / r["modules"][module]
                                    for b, v in r["buckets"].get(module, {}).items()}
         for step, module in scopes.SHOWN if r["modules"].get(module)})
    return r


def per_step_ms(run, module: str, which: str):
    """Device ms per execution of ``module`` in the traced window in bucket
    ``which``; None without a trace, without executions, or where no op of
    the module carries that name."""
    r = layers(run)
    if not r or not r["modules"].get(module):
        return None
    by = r["buckets"].get(module, {})
    if which not in by:
        return None
    return 1e3 * by[which] / r["modules"][module]
