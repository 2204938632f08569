"""Device time per decode step in the `mlp` scope: the SwiGLU feed-forward."""

from harness.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "jit_decode_step", "mlp")
