"""Device time per decode step in the layer scan's own ops (slicing each
layer's weights and cache out of the stack, stacking the new cache, the loop
carry's copies): ops under the `layers` scope with no inner name."""

from harness.scopes import per_step_ms


def read(run):
    return per_step_ms(run, "jit_decode_step", "layer_scan")
