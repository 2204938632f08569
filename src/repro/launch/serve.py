"""Serving driver: model weights staged through dynamically provisioned
storage, then batched prefill + decode.

The serving-side use of the paper's mechanism: at scale, thousands of
serving replicas hammering the global FS for weight loads is the same
burst problem as checkpoint writes — so weights are staged ONCE from the
global FS into a job-scoped EphemeralFS and every local replica loads from
the burst tier (modeled time reported), then requests are decoded with a
KV cache.

Prefill and decode are the sharded step builders of ``runtime/parallel.py``
over a (data=1, model=--tp) mesh; with --tp 1 that is one device. Weights are
initialised sharded, published, dropped and restored from the burst tier
onto their shardings, so one copy is on the devices at a time.

Run:  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-14b --requests 8
      python -m repro.launch.serve --arch phi4-mini-3.8b --full --kernels    (TPU)
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..checkpoint import CheckpointManager
from ..core import (
    GlobalFS,
    JobRequest,
    Provisioner,
    Scheduler,
    StorageRequest,
    Workload,
    dom_cluster,
    predict_read,
)
from ..core.staging import stage_tree
from ..kernels.decode_attention import BLOCK_K
from ..models import build_model
from ..runtime import RuntimeConfig, jit_decode_step, jit_prefill
from .common import device_label, enable_compile_cache, model_config
from .mesh import make_host_mesh


def cache_len(n: int) -> int:
    """Decode cache length for ``n`` positions: the decode kernel streams the
    cache in blocks of min(BLOCK_K, length), so a longer cache is rounded up
    to a multiple of BLOCK_K."""
    return n if n <= BLOCK_K else -(-n // BLOCK_K) * BLOCK_K


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-14b")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers (widths unchanged)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=48)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--kernels", action="store_true",
                    help="RuntimeConfig.use_kernels: Pallas attention kernels")
    ap.add_argument("--tp", type=int, default=1,
                    help="devices on the mesh's model axis")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = model_config(args.arch, full=not args.smoke, layers=args.layers)
    model = build_model(cfg)
    rt = RuntimeConfig(use_kernels=args.kernels)
    mesh = make_host_mesh(1, args.tp)

    B, P, G = args.requests, args.prompt_len, args.gen
    prompts = jax.random.randint(jax.random.PRNGKey(1), (B, P), 0, cfg.vocab_size)
    batch = {"tokens": prompts}
    if cfg.family == "vlm":
        batch["patch_embeds"] = jnp.zeros((B, cfg.n_patches, cfg.d_model), cfg.dtype)
    if cfg.family == "audio":
        batch["frames"] = jnp.zeros((B, cfg.encoder_seq, cfg.d_model), cfg.dtype)
    S_max = cache_len(P + G + (cfg.n_patches if cfg.family == "vlm" else 0))

    key = jax.random.PRNGKey(args.seed)
    params_like = jax.eval_shape(model.init, key)
    cache_like = jax.eval_shape(
        lambda p, b: model.prefill(p, b, S_max), params_like, batch)[1]
    tok_like = {"token": jax.ShapeDtypeStruct((B,), jnp.int32)}
    prefill, p_sh, b_sh, _ = jit_prefill(
        model, mesh, rt, S_max, params_like, batch, cache_like)
    decode, _, _, tok_sh = jit_decode_step(
        model, mesh, rt, params_like, cache_like, tok_like)

    cluster = dom_cluster()
    sched = Scheduler(cluster)
    alloc = sched.submit(JobRequest("serve", 8, storage=StorageRequest(nodes=2)))
    prov = Provisioner(cluster)
    dep = prov.deploy(prov.plan_for(alloc))
    gfs = GlobalFS()
    try:
        # -- publish weights to the global FS (the model registry) ----------
        params = jax.jit(model.init, out_shardings=p_sh)(key)
        pub = CheckpointManager(gfs, root="/registry/models")
        man = pub.save(0, {"params": params})
        del params
        print(f"[registry] published {man['total_bytes']/1e6:.1f} MB to global FS")

        # -- stage global -> burst (one registry read feeds all replicas) ---
        burst = CheckpointManager(dep.fs, root="/weights", global_fs=gfs)
        rep = stage_tree(gfs, dep.fs, "/registry/models/step-00000000",
                         "/weights/step-00000000",
                         src_model=gfs.perf_view(), dst_model=dep.model)
        loaded, _ = burst.restore({"params": params_like},
                                  shardings={"params": p_sh})
        params = loaded["params"]
        # modeled: 256 hosts each reading the weights from the burst tier (FPP)
        w = Workload(n_procs=256, size_per_proc=man["total_bytes"], pattern="fpp")
        t_all = predict_read(w, dep.model).elapsed_s
        print(f"[stage-in] {rep.bytes/1e6:.1f} MB staged "
              f"(modeled {rep.modeled_time_s:.2f}s); 256-replica load from burst "
              f"modeled {t_all:.2f}s")

        # -- serve ----------------------------------------------------------
        t0 = time.perf_counter()
        logits, cache = prefill(params, jax.device_put(batch, b_sh))
        first_logits = np.asarray(logits, np.float32)
        tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        out = [tok]
        for _ in range(G - 1):
            logits, cache = decode(params, cache,
                                   jax.device_put({"token": tok}, tok_sh))
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            out.append(tok)
        gen = np.asarray(jnp.stack(out, axis=1))
        dt = time.perf_counter() - t0
        path = "kernels" if rt.use_kernels else "jnp"
        print(f"[serve] {B} requests x {G} tokens ({path}) in {dt:.2f}s "
              f"({device_label(mesh.size)}, incl. compile)")
    finally:
        dep.teardown()
        sched.release(alloc)
        gfs.teardown()
    return {"generated": gen, "first_logits": first_logits,
            "stage_bytes": rep.bytes, "load_modeled_s": t_all}


if __name__ == "__main__":
    main()
