"""Decode's share of its roofline: per step the larger of operations over
peak FLOP/s and bytes (weights, valid cache, new K/V) over peak HBM
bandwidth, summed, over the decode spans."""

from harness import costs


def read(run):
    spans = run.spans.get("decode_step")
    if not spans:
        return None
    d, B, P, pk = run.dims, run.data["B"], run.data["P"], run.peaks
    least = sum(max(costs.decode_flops(d, B, P + j - 1, run.data["S_max"])
                    / pk["bf16_flops_per_s"],
                    costs.decode_bytes(d, B, P + j - 1) / pk["hbm_bytes_per_s"])
                for G in run.data["wave_steps"] for j in range(1, G))
    return 100.0 * least / sum(b - a for a, b in spans)
