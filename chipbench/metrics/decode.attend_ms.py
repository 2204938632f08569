"""Device time per decode step in the `attend` scope: attention over the cache."""

from harness.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "jit_decode_step", "attend")
