"""Device time per decode step in the `ssm_state` scope: every Mamba layer's
state read out of the carried stack, stepped once, read by C and written
back."""

from harness.ssm_scopes import per_step_ms


def read(run):
    return per_step_ms(run, "jit_decode_step", "ssm_state")
