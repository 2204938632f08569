"""Device time per decode step in the `ssm_proj` scope: the Mamba layers'
in/out projections, the gate and the gated norm."""

from harness.ssm_scopes import per_step_ms


def read(run):
    return per_step_ms(run, "jit_decode_step", "ssm_proj")
