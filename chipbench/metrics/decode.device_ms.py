"""Device time of one decode step: the ops of jit_decode_step in the traced
window, in every bucket, over that module's executions there."""

from harness.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "jit_decode_step")
