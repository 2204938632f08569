"""Device time per decode step in the `lm_head` scope: the projection to the
vocabulary."""

from harness.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "jit_decode_step", "lm_head")
