"""Prefill's share of the chip's peak FLOP/s: the operations the prompt
needs (causal attention), counted from shapes, over the prefill spans."""

from harness import costs


def read(run):
    spans = run.spans.get("prefill")
    if not spans:
        return None
    t = sum(b - a for a, b in spans)
    flops = len(spans) * costs.prefill_flops(run.dims, run.data["B"], run.data["P"])
    return 100.0 * flops / t / run.peaks["bf16_flops_per_s"]
