"""Device time per decode step in the `attn_proj` scope: q/k/v and output
projections, rope."""

from harness.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "jit_decode_step", "attn_proj")
