"""End-to-end training driver: storage provisioning + data staging +
distributed train loop + burst checkpointing + fault-tolerant restart.

This is the paper's workflow as a training job:
  1. request compute + storage allocations (scheduler);
  2. provision the EphemeralFS on the granted storage nodes;
  3. stage the corpus in from the global FS;
  4. train with periodic checkpoints to the burst tier, drained to the
     global FS in the background;
  5. on restart (--resume), restore the newest committed checkpoint.

CPU-friendly by design: defaults are a tiny config on one device;
``--arch`` selects any assigned architecture, ``--full`` its published config
and ``--layers`` cuts only its depth, to fit one chip.
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
    dom_cluster,
    size_for_checkpoint,
)
from ..data import DatasetSpec, Loader, stage_in, write_corpus
from ..models import build_model
from ..optim import AdamWConfig
from ..runtime import RuntimeConfig, TrainState, make_train_state, make_train_step
from .common import device_label, enable_compile_cache, model_config


def tree_bytes(tree) -> int:
    return sum(x.size * x.dtype.itemsize for x in jax.tree.leaves(tree))


def main(argv=None) -> dict:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-moe-1b-a400m")
    ap.add_argument("--smoke", action="store_true", default=True)
    ap.add_argument("--full", dest="smoke", action="store_false")
    ap.add_argument("--layers", type=int, default=None,
                    help="cut the depth to this many layers (widths unchanged)")
    ap.add_argument("--steps", type=int, default=20)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--ckpt-every", type=int, default=10)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--storage-nodes", type=int, default=2)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = model_config(args.arch, full=not args.smoke, layers=args.layers)
    model = build_model(cfg)
    rt = RuntimeConfig(remat="dots", zero1=False,
                       opt=AdamWConfig(lr=args.lr), schedule="warmup_cosine")

    # -- storage provisioning (the paper's §III flow) -----------------------
    cluster = dom_cluster()
    sched = Scheduler(cluster)
    state = jax.jit(lambda r: make_train_state(model, r, rt))(
        jax.random.PRNGKey(args.seed))
    ckpt_bytes = tree_bytes(state.params) + tree_bytes(state.opt.master) * 3
    storage_req = StorageRequest(nodes=args.storage_nodes)
    alloc = sched.submit(JobRequest("train-lm", n_compute=8, storage=storage_req))
    prov = Provisioner(cluster)
    dep = prov.deploy(prov.plan_for(alloc))
    print(f"[provision] {len(alloc.storage_nodes)} storage nodes, "
          f"modeled deploy {dep.deploy_time_s:.2f}s "
          f"(ckpt size {ckpt_bytes/1e6:.1f} MB)")

    gfs = GlobalFS()
    try:
        spec = DatasetSpec(seed=7, vocab=cfg.vocab_size,
                           n_tokens=max(1 << 18, args.batch * (args.seq + 1) * 4))
        write_corpus(gfs, "/datasets/train", spec)
        rep = stage_in(gfs, dep.fs, "/datasets/train", "/data",
                       src_model=gfs.perf_view(), dst_model=dep.model)
        print(f"[stage-in] {rep.files} files, {rep.bytes/1e6:.1f} MB, "
              f"modeled {rep.modeled_time_s:.2f}s")

        loader = Loader(spec, batch=args.batch, seq=args.seq, fs=dep.fs, root="/data")
        mgr = CheckpointManager(dep.fs, global_fs=gfs)

        # -- resume -------------------------------------------------------------
        start_step = 0
        if args.resume and mgr.steps():
            restored, start_step = mgr.restore({"params": state.params, "opt": state.opt})
            state = TrainState(restored["params"], restored["opt"], state.ef)
            print(f"[resume] restored committed step {start_step}")

        step_fn = jax.jit(make_train_step(model, rt), donate_argnums=(0,))
        eval_fn = jax.jit(lambda p, b: model.loss(p, b)[0])

        def to_jax(batch):
            jbatch = {k: jnp.asarray(v) for k, v in batch.items()}
            if cfg.family == "vlm":
                jbatch["patch_embeds"] = jnp.zeros(
                    (args.batch, cfg.n_patches, cfg.d_model), jnp.float32)
            if cfg.family == "audio":
                jbatch["frames"] = jnp.zeros(
                    (args.batch, cfg.encoder_seq, cfg.d_model), jnp.float32)
            return jbatch

        eval_batch = to_jax(loader.batch_at(0))
        eval_before = float(eval_fn(state.params, eval_batch))

        losses = []
        t0 = time.time()
        for step in range(start_step, args.steps):
            jbatch = to_jax(loader.batch_at(step))
            state, metrics = step_fn(state, jbatch)
            loss = float(metrics["loss"])
            losses.append(loss)
            if (step + 1) % args.ckpt_every == 0 or step + 1 == args.steps:
                man = mgr.save(step + 1, {"params": state.params, "opt": state.opt})
                drain = mgr.drain_to_global(step + 1)
                print(f"[ckpt] step {step+1}: {man['total_bytes']/1e6:.1f} MB to burst; "
                      f"drain modeled {drain['modeled_time_s']:.3f}s")
            if step % 5 == 0 or step + 1 == args.steps:
                print(f"step {step:4d} loss {loss:.4f} "
                      f"gnorm {float(metrics['grad_norm']):.3f}")

        wall = time.time() - t0
        eval_after = float(eval_fn(state.params, eval_batch))
        print(f"[done] {args.steps - start_step} steps in {wall:.1f}s "
              f"({device_label()}, incl. compile); "
              f"held-batch loss {eval_before:.4f} -> {eval_after:.4f}")

        result = {
            "losses": losses,
            "eval_before": eval_before,
            "eval_after": eval_after,
            "steps": mgr.steps(),
            "deploy_time_s": dep.deploy_time_s,
            "improved": eval_after < eval_before,
        }
    finally:
        dep.teardown()
        sched.release(alloc)
        gfs.teardown()
    return result


if __name__ == "__main__":
    main()
