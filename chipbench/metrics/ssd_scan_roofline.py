"""The prefill SSD's share of its roofline: per Mamba layer the larger of its
operations (causal pairs within each chunk, chunk states, state reads) over
peak FLOP/s and its bytes (x, B, C, dt in, y out, final state) over peak HBM
bandwidth, over the device time of the `ssm_scan` scope per prefill."""

from harness import costs_hybrid as C
from harness.reference_hybrid import HDims
from harness.ssm_scopes import per_step_ms


def read(run):
    ms = per_step_ms(run, "jit_prefill", "ssm_scan")
    if not ms:
        return None
    d, B, P, pk = HDims.of(run.cell.config), run.data["B"], run.data["P"], run.peaks
    least = d.n_mamba * max(C.ssd_flops(d, B, P) / pk["bf16_flops_per_s"],
                            C.ssd_bytes(d, B, P) / pk["hbm_bytes_per_s"])
    return 100.0 * least / (ms * 1e-3)
