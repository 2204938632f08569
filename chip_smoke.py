"""Run the model layer's main path on a TPU through its normal entry points.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # the sharded path, on a four-chip host

One chip: the Pallas attention kernels against their float32 references at
phi4-mini-3.8b widths; ``repro.launch.serve.main`` serving phi4-mini-3.8b at
its full config, once with the kernels and once with the jnp path on the same
seeded weights; ``repro.launch.train.main`` taking a few steps of
granite-moe-1b-a400m at published widths, cut to 8 layers.

Four chips: ``serve.main`` serving qwen3-14b at its full config on a
(data=1, model=4) mesh, and its 2-layer cut served on that mesh against the
same cut on one device.

Every phase prints one line with its outcome and its wall time. The wall
time includes set-up and compilation; it is not a benchmark. No phase catches
its own failure: the script exits 0 only if every phase passed, and it exits
non-zero at once where JAX finds no TPU. Its last line is one JSON object
naming the device.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))

# Kernel outputs are bf16; this is the bf16 tolerance of tests/test_kernels.py.
KERNEL_ATOL = KERNEL_RTOL = 2e-2
# Two paths through a bf16 model with random weights: logits have std ~1, and
# kernel vs jnp differences at phi4 widths, 8 and 16 layers on the CPU, had
# mean |diff| 0.013-0.014 and max 0.078-0.090. A wrong kernel moves them by ~1.
LOGIT_ATOL = 0.25

SERVE = ["--full", "--requests", "8", "--prompt-len", "512", "--gen", "32"]


def require(ok, msg):
    """A failed check ends the run (unlike ``assert``, also under -O)."""
    if not ok:
        raise RuntimeError(msg)


def phase(name):
    """Print ``name``'s outcome and wall time when the wrapped call returns;
    an exception propagates and ends the script non-zero."""
    def wrap(fn):
        def run(*a, **kw):
            t0 = time.perf_counter()
            detail = fn(*a, **kw)
            wall = time.perf_counter() - t0
            print(f"[phase] {name}: ok; {detail} "
                  f"(wall {wall:.1f}s incl. set-up and compile, not a benchmark)",
                  flush=True)
        return run
    return wrap


def check_device(chips: int):
    import jax
    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        sys.exit(f"[phase] device: FAILED: JAX reports {d.platform!r}, not a TPU")
    if len(devs) < chips:
        sys.exit(f"[phase] device: FAILED: {len(devs)} devices, --chips {chips}")
    free = shutil.disk_usage(tempfile.gettempdir()).free
    print(f"[phase] device: ok; {d.device_kind} x{len(devs)}, "
          f"{free/1e9:.1f} GB free under {tempfile.gettempdir()}", flush=True)
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def _max_excess(got, want, atol, rtol):
    import numpy as np
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    require(got.shape == want.shape and np.isfinite(got).all(),
            f"shape {got.shape} vs {want.shape}, or non-finite values")
    err = np.abs(got - want)
    bad = err > atol + rtol * np.abs(want)
    require(not bad.any(), f"{int(bad.sum())} values off, max |err| {err.max():.4g}")
    return float(err.max())


@phase("kernels vs reference")
def check_kernels():
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config
    from repro.kernels import ops, ref

    cfg = get_config("phi4-mini-3.8b")
    H, K, hd, S, B = cfg.n_heads, cfg.n_kv_heads, cfg.hd, 2048, 2
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, S, H, hd), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, S, K, hd), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, S, K, hd), jnp.bfloat16)
    qd = jax.random.normal(ks[3], (B, 1, H, hd), jnp.bfloat16)
    kv_len = 1500
    for fn, args, kw in ((ops.flash_attention, (q, k, v), {}),
                         (ops.decode_attention, (qd, k, v), {"kv_len": kv_len})):
        text = fn.lower(*args, **kw).compile().as_text()
        require("tpu_custom_call" in text, f"{fn.__name__} did not lower to Mosaic")

    f32 = lambda *xs: [x.astype(jnp.float32) for x in xs]  # noqa: E731
    with jax.default_matmul_precision("highest"):
        want_fa = ref.flash_attention_ref(*f32(q, k, v), causal=True)
        want_da = ref.decode_attention_ref(*f32(qd, k, v), kv_len=kv_len)
    e_fa = _max_excess(ops.flash_attention(q, k, v), want_fa, KERNEL_ATOL, KERNEL_RTOL)
    e_da = _max_excess(ops.decode_attention(qd, k, v, kv_len=kv_len), want_da,
                       KERNEL_ATOL, KERNEL_RTOL)
    return (f"native Mosaic kernels at H={H} K={K} hd={hd} S=T={S} bf16; max |err| "
            f"flash {e_fa:.4g}, decode {e_da:.4g} "
            f"(tol {KERNEL_ATOL} + {KERNEL_RTOL}*|ref|, ref in float32)")


def _compare_serving(a, b, what):
    """Last-position prefill logits agree within LOGIT_ATOL, and each
    request's first generated token is the same on both paths. Random weights
    leave near-ties among 10^5 logits, which two correct bf16 paths may break
    differently; a differing token passes only where the token each path chose
    is within LOGIT_ATOL of the best logit on the other path."""
    import numpy as np
    la, lb = a["first_logits"], b["first_logits"]
    err = _max_excess(la, lb, LOGIT_ATOL, 0.0)
    ta, tb = a["generated"][:, 0], b["generated"][:, 0]
    rows = np.arange(len(ta))
    tied = (lb[rows, ta] >= lb.max(-1) - LOGIT_ATOL) & (la[rows, tb] >= la.max(-1) - LOGIT_ATOL)
    require(((ta == tb) | tied).all(), f"{what}: first tokens {ta} vs {tb}")
    same = int((ta == tb).sum())
    rest = "" if same == len(ta) else ", the rest near-ties within tol on both paths"
    return (f"{what}: last-position logits max |diff| {err:.4g} (tol {LOGIT_ATOL}); "
            f"first token equal for {same} of {len(ta)} requests{rest}")


@phase("serve phi4-mini-3.8b full config, kernels vs jnp")
def check_serve():
    from repro.launch import serve
    kern = serve.main(["--arch", "phi4-mini-3.8b", "--kernels", *SERVE])
    ref = serve.main(["--arch", "phi4-mini-3.8b", *SERVE])
    require(kern["generated"].shape == (8, 32), kern["generated"].shape)
    return (f"8 requests x 512 prompt + 32 generated, weights "
            f"{kern['stage_bytes']/1e9:.2f} GB staged and restored; "
            + _compare_serving(kern, ref, "kernels vs jnp"))


@phase("train granite-moe-1b-a400m, 8 of 24 layers")
def check_train():
    import math
    from repro.launch import train
    res = train.main(["--arch", "granite-moe-1b-a400m", "--full", "--layers", "8",
                      "--steps", "4", "--batch", "8", "--seq", "1024",
                      "--ckpt-every", "4"])
    losses = res["losses"]
    require(len(losses) == 4 and all(math.isfinite(x) for x in losses), losses)
    require(res["steps"] == [4], res["steps"])
    return (f"4 steps at batch 8 x seq 1024, losses "
            + ", ".join(f"{x:.4f}" for x in losses)
            + "; checkpoint of step 4 committed on the burst tier")


@phase("serve qwen3-14b full config on a (data=1, model=4) mesh")
def check_sharded_serve():
    import numpy as np
    from repro.launch import serve
    res = serve.main(["--arch", "qwen3-14b", "--tp", "4", *SERVE])
    require(res["generated"].shape == (8, 32), res["generated"].shape)
    require(np.isfinite(res["first_logits"]).all(), "non-finite logits")
    return f"8 requests x 512 prompt + 32 generated, {res['stage_bytes']/1e9:.2f} GB of weights"


@phase("qwen3-14b cut to 2 layers, model=4 mesh vs one device")
def check_sharded_vs_one():
    from repro.launch import serve
    cut = ["--arch", "qwen3-14b", "--layers", "2", *SERVE]
    return _compare_serving(serve.main([*cut, "--tp", "4"]), serve.main([*cut, "--tp", "1"]),
                            "4 chips vs 1")


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    from repro.launch.common import enable_compile_cache
    enable_compile_cache()
    device = check_device(args.chips)
    if args.chips == 4:
        check_sharded_serve()
        check_sharded_vs_one()
    else:
        check_kernels()
        check_serve()
        check_train()
    print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
