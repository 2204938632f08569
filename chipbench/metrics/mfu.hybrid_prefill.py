"""The hybrid prefill's share of the chip's peak FLOP/s: the operations the
prompt needs (projections, causal attention, the SSD over causal pairs
within each chunk, MLPs), counted from shapes, over the prefill spans."""

from harness import costs_hybrid
from harness.reference_hybrid import HDims


def read(run):
    spans = run.spans.get("prefill")
    if not spans:
        return None
    t = sum(b - a for a, b in spans)
    flops = len(spans) * costs_hybrid.prefill_flops(
        HDims.of(run.cell.config), run.data["B"], run.data["P"])
    return 100.0 * flops / t / run.peaks["bf16_flops_per_s"]
