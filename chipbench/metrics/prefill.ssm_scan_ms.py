"""Device time per prefill in the `ssm_scan` scope: every Mamba layer's
chunked SSD over the prompt."""

from harness.ssm_scopes import per_step_ms


def read(run):
    return per_step_ms(run, "jit_prefill", "ssm_scan")
