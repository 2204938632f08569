"""From a profiler trace to device busy time, idle gaps and top operations.

``extract`` reads the ``.xplane.pb`` the JAX profiler writes into plain
lists; ``reduce`` works on those lists alone, so the CPU self-tests check it
on a small recorded extract. Host spans are the benchmark's own
``TraceAnnotation``s, named ``cb:<span>``; ``cb:window`` marks the measured
window, and every device interval is clipped to it.
"""

from __future__ import annotations

import bisect
import glob
import os
from collections import defaultdict

PREFIX = "cb:"
SHORT_NS = 10_000     # idle gaps shorter than this are counted as between_ops
# control-flow ops span the ops of their bodies, which the trace lists too
CONTAINERS = ("while", "conditional", "call")


def extract(trace_dir: str) -> dict:
    """{"devices": {plane: [[op, start_ns, dur_ns], ...]},
        "host": [[span, start_ns, dur_ns], ...]} from the newest trace."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not files:
        raise RuntimeError(f"no profiler trace under {trace_dir}")
    pd = ProfileData.from_file(files[-1])
    devices, host = {}, []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {ln.name: ln for ln in plane.lines}
            ops = lines.get("XLA Ops")
            if ops is None:
                continue
            devices[plane.name] = [[_op_name(e.name), float(e.start_ns),
                                    float(e.duration_ns)] for e in ops.events]
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                for e in ln.events:
                    if e.name.startswith(PREFIX):
                        host.append([e.name[len(PREFIX):], float(e.start_ns),
                                     float(e.duration_ns)])
    return {"devices": devices, "host": host}


def _op_name(text: str) -> str:
    """``%fusion.12 = bf16[...] fusion(...)`` -> ``fusion.12``."""
    return text.split(" = ", 1)[0].lstrip("%")[:80]


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _label(spans, starts, t):
    """The innermost benchmark span that holds time ``t``: spans of one
    thread nest, so it is the latest-starting one that has not ended."""
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0:
        name, s, e = spans[i]
        if e >= t and name != "window":
            return name
        i -= 1
    return "none"


def reduce(ext: dict, top: int = 10) -> dict:
    """busy_s (averaged over the chips), window_s, device_ops and idle_gaps."""
    host = sorted((s, s + d, n) for n, s, d in ext["host"])
    host = [(n, s, e) for s, e, n in host]
    starts = [s for _, s, _ in host]
    win = [h for h in host if h[0] == "window"]
    if not win or not ext["devices"]:
        raise RuntimeError("trace holds no window span or no device operations")
    w0, w1 = win[0][1], win[0][2]
    busy_total = 0.0
    op_time = defaultdict(float)
    gaps = defaultdict(float)
    for ops in ext["devices"].values():
        iv = []
        for name, s, d in ops:
            s2, e2 = max(s, w0), min(s + d, w1)
            if e2 > s2:
                iv.append((s2, e2))
                if not name.startswith(CONTAINERS):
                    op_time[name] += (e2 - s2) * 1e-9
        u = _union(iv)
        busy_total += sum(e - s for s, e in u)
        edges = [w0] + [x for se in u for x in se] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b - a >= SHORT_NS:
                gaps[_label(host, starts, (a + b) / 2)] += (b - a) * 1e-9
            elif b > a:
                gaps["between_ops"] += (b - a) * 1e-9
    n = len(ext["devices"])
    return {
        "busy_s": busy_total * 1e-9 / n,
        "window_s": (w1 - w0) * 1e-9,
        "device_ops": sorted(([k, v / n] for k, v in op_time.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v / n] for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }
