"""Compile a cell's programs for a described TPU v5e and print their memory.

    JAX_PLATFORMS=cpu python chipbench/rehearse.py --workload phi4-chat --batch 16 24

No chip is needed: the TPU compiler runs here for a chip that is described,
not attached. For each batch size it compiles what the cell's window drives,
the prefill and the decode step, at the cell's shapes, and prints ``memory_analysis()``: arguments, outputs, temporaries
and aliased bytes, and their sum less the aliases, beside the chip's 16 GB.
What the process keeps besides (a second cache while a wave turns over) is
not counted.
"""

from __future__ import annotations

import argparse
import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(HERE), "src"))
sys.path.insert(0, HERE)


def mem(compiled) -> dict:
    m = compiled.memory_analysis()
    out = {k: int(getattr(m, k + "_size_in_bytes")) for k in
           ("argument", "output", "temp", "alias", "generated_code")}
    out["total"] = out["argument"] + out["output"] + out["temp"] - out["alias"]
    return out


def show(what: str, B: int, m: dict):
    gb = {k: f"{v / 1e9:.2f}" for k, v in m.items()}
    print(f"{what} B={B}: total {gb['total']} GB (args {gb['argument']}, out {gb['output']}, "
          f"temp {gb['temp']}, alias {gb['alias']})", flush=True)


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--batch", type=int, nargs="+", required=True)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh

    from harness import spec
    from harness.core import program_config
    from repro.launch.serve import cache_len
    from repro.models import build_model
    from repro.runtime import RuntimeConfig, jit_decode_step, jit_prefill

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    dev = topo.devices[0]
    cell = spec.find_cell(args.workload)
    cfg = program_config(cell.config)
    model = build_model(cfg)
    mix = cell.traffic
    key = jax.random.PRNGKey(0)
    params_like = jax.eval_shape(model.init, key)
    sds = jax.ShapeDtypeStruct

    rt = RuntimeConfig()
    mesh = Mesh(np.array([dev]).reshape(1, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    P = mix["prompt_tokens"]
    S_max = cache_len(P + mix["output_tokens"]["hi"])
    for B in args.batch:
        batch = {"tokens": sds((B, P), jnp.int32)}
        cache_like = jax.eval_shape(
            lambda p, b: model.prefill(p, b, S_max), params_like, batch)[1]
        tok = {"token": sds((B,), jnp.int32)}
        prefill, *_ = jit_prefill(model, mesh, rt, S_max, params_like, batch, cache_like)
        decode, *_ = jit_decode_step(model, mesh, rt, params_like, cache_like, tok)
        show("prefill", B, mem(prefill.lower(params_like, batch).compile()))
        show("decode", B, mem(decode.lower(params_like, cache_like, tok).compile()))


if __name__ == "__main__":
    main()
