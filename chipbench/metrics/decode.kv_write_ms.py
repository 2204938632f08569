"""Device time per decode step in the `kv_write` scope: the new K and V written
into the cache."""

from harness.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "jit_decode_step", "kv_write")
