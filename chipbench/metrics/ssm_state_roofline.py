"""The decode state update's share of its roofline: every Mamba layer's
float32 state read and written once, over peak HBM bandwidth, over the
device time of the `ssm_state` scope per decode step."""

from harness import costs_hybrid
from harness.reference_hybrid import HDims
from harness.ssm_scopes import per_step_ms


def read(run):
    ms = per_step_ms(run, "jit_decode_step", "ssm_state")
    if not ms:
        return None
    least = costs_hybrid.state_step_bytes(HDims.of(run.cell.config), run.data["B"]) \
        / run.peaks["hbm_bytes_per_s"]
    return 100.0 * least / (ms * 1e-3)
