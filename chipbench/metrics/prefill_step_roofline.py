"""Prefill's share of its roofline: the larger of operations over peak FLOP/s
and bytes over peak HBM bandwidth, over the prefill spans."""

from harness import costs


def read(run):
    spans = run.spans.get("prefill")
    if not spans:
        return None
    t = sum(b - a for a, b in spans)
    d, B, P, pk = run.dims, run.data["B"], run.data["P"], run.peaks
    least = max(costs.prefill_flops(d, B, P) / pk["bf16_flops_per_s"],
                costs.prefill_bytes(d, B, P) / pk["hbm_bytes_per_s"])
    return 100.0 * len(spans) * least / t
