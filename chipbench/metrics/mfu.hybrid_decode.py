"""The hybrid decode's share of the chip's peak FLOP/s: each step's
operations (state updates, the valid KV cache only), counted from shapes,
over the decode spans."""

from harness import costs_hybrid
from harness.reference_hybrid import HDims


def read(run):
    spans = run.spans.get("decode_step")
    if not spans:
        return None
    d, B, P = HDims.of(run.cell.config), run.data["B"], run.data["P"]
    flops = sum(costs_hybrid.decode_flops(d, B, P + j - 1, run.data["S_max"])
                for G in run.data["wave_steps"] for j in range(1, G))
    t = sum(b - a for a, b in spans)
    return 100.0 * flops / t / run.peaks["bf16_flops_per_s"]
