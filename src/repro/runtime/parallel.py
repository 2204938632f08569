"""Distributed step builders: train_step / prefill / decode_step, jitted with
explicit NamedShardings over the production mesh.

The train step is ZeRO-1-ready (optimizer state shardings extend over the
"data" axis) with optional int8+error-feedback gradient compression and a
remat policy knob. Buffers are donated (params/opt-state update in place).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .. import optim
from ..hints import constrain, mesh_hint
from ..models.common import Model
from . import sharding as sh


@dataclasses.dataclass(frozen=True)
class RuntimeConfig:
    remat: Optional[str] = "full"          # None | "dots" | "full"
    use_kernels: bool = False              # Pallas kernels (TPU) vs jnp ref
    compress_grads: bool = False           # int8 + error feedback
    zero1: bool = True                     # shard opt state over "data"
    donate: bool = True
    accum: int = 1                         # gradient-accumulation microbatches
    flags: tuple = ()                      # trace-time variant switches (hints.flag)
    schedule: str = "warmup_cosine"
    opt: optim.AdamWConfig = dataclasses.field(default_factory=optim.AdamWConfig)


class TrainState(NamedTuple):
    params: Any
    opt: optim.OptState
    ef: Any                                # ErrorFeedback | () when disabled


def make_train_state(model: Model, rng, rt: RuntimeConfig) -> TrainState:
    params = model.init(rng)
    opt = optim.init(params)
    ef = optim.ef_init(params) if rt.compress_grads else ()
    return TrainState(params, opt, ef)


def train_state_shardings(mesh: Mesh, state_like: TrainState, rt: RuntimeConfig):
    ps = sh.param_shardings(mesh, state_like.params)
    os_ = sh.opt_shardings(mesh, state_like.opt, ps, zero1=rt.zero1)
    if rt.compress_grads:
        ef = optim.ErrorFeedback(
            jax.tree.map(lambda s: s, os_.m)  # residuals shadow m's sharding
        )
    else:
        ef = ()
    return TrainState(ps, os_, ef)


def _schedule(rt: RuntimeConfig) -> Callable:
    if rt.schedule == "warmup_cosine":
        return optim.warmup_cosine
    return optim.constant


def make_train_step(model: Model, rt: RuntimeConfig) -> Callable:
    sched = _schedule(rt)

    def grads_of(params, batch):
        def loss_fn(p):
            return model.loss(p, batch, remat=rt.remat, use_kernels=rt.use_kernels)

        return jax.value_and_grad(loss_fn, has_aux=True)(params)

    def train_step(state: TrainState, batch):
        if rt.accum > 1:
            # microbatch over the leading batch dim: activation memory / accum
            def split(x):
                return x.reshape(rt.accum, x.shape[0] // rt.accum, *x.shape[1:])

            micro = jax.tree.map(split, batch)
            micro = jax.tree.map(
                lambda x: constrain(x, None, "dp"), micro
            )
            # fp32 accumulator is 4 bytes/param sharded over "model" only —
            # 2x8.2 GB/device for a 32B model. accbf16 halves it (loss-scale
            # safe at accum<=8; see EXPERIMENTS.md §Perf B).
            acc_dt = jnp.bfloat16 if "accbf16" in rt.flags else jnp.float32
            g0 = jax.tree.map(
                lambda p: jnp.zeros(p.shape, acc_dt), state.params
            )

            def body(carry, mb):
                acc, loss_acc = carry
                (loss, _), g = grads_of(state.params, mb)
                acc = jax.tree.map(
                    lambda a, gg: a + gg.astype(acc_dt), acc, g
                )
                return (acc, loss_acc + loss), None

            (gsum, loss_sum), _ = jax.lax.scan(body, (g0, 0.0), micro)
            grads = jax.tree.map(lambda g: g / rt.accum, gsum)
            loss = loss_sum / rt.accum
            metrics = {"ce": loss, "aux": jnp.zeros(())}
        else:
            (loss, metrics), grads = grads_of(state.params, batch)

        ef = state.ef
        if rt.compress_grads:
            grads, ef, _ = optim.compress_grads(grads, state.ef)
        params, opt, stats = optim.update(
            grads, state.opt, state.params, rt.opt, lr_scale=sched(state.opt.step)
        )
        out_metrics = {"loss": loss, **metrics, **stats}
        return TrainState(params, opt, ef), out_metrics

    return train_step


def jit_train_step(
    model: Model,
    mesh: Mesh,
    rt: RuntimeConfig,
    state_like: TrainState,
    batch_like: dict,
):
    """Returns (jitted_step, state_shardings, batch_shardings)."""
    st_sh = train_state_shardings(mesh, state_like, rt)
    b_sh = sh.batch_shardings(mesh, batch_like)
    metric_sh = NamedSharding(mesh, P())
    raw_step = make_train_step(model, rt)

    def train_step(state, batch):               # module jit_train_step
        with mesh_hint(mesh, rt.flags):
            return raw_step(state, batch)

    step = jax.jit(
        train_step,
        in_shardings=(st_sh, b_sh),
        out_shardings=(st_sh, None),
        donate_argnums=(0,) if rt.donate else (),
    )
    return step, st_sh, b_sh


# -- serving -----------------------------------------------------------------
def make_prefill(model: Model, S_max: int, rt: RuntimeConfig) -> Callable:
    def prefill(params, batch):
        return model.prefill(params, batch, S_max, use_kernels=rt.use_kernels)

    return prefill


def make_decode_step(model: Model, rt: RuntimeConfig) -> Callable:
    def decode(params, cache, batch):
        return model.decode_step(params, cache, batch, use_kernels=rt.use_kernels)

    return decode


def jit_decode_step(
    model: Model,
    mesh: Mesh,
    rt: RuntimeConfig,
    params_like,
    cache_like,
    batch_like,
):
    if "dp_decode" in rt.flags:
        # small-model serving: replicate weights, shard batch only — no
        # model-axis decisions left to GSPMD (see EXPERIMENTS.md §Perf C)
        p_sh = sh.replicated(mesh, params_like)
    else:
        p_sh = sh.param_shardings(mesh, params_like)
    c_sh = sh.cache_shardings(mesh, cache_like, model.cfg)
    b_sh = sh.batch_shardings(mesh, batch_like)
    raw_step = make_decode_step(model, rt)

    def decode_step(params, cache, batch):      # module jit_decode_step
        with mesh_hint(mesh, rt.flags):
            return raw_step(params, cache, batch)

    step = jax.jit(
        decode_step,
        in_shardings=(p_sh, c_sh, b_sh),
        out_shardings=(None, c_sh),
        donate_argnums=(1,) if rt.donate else (),
    )
    return step, p_sh, c_sh, b_sh


def jit_prefill(
    model: Model,
    mesh: Mesh,
    rt: RuntimeConfig,
    S_max: int,
    params_like,
    batch_like,
    cache_like,
):
    p_sh = sh.param_shardings(mesh, params_like)
    b_sh = sh.batch_shardings(mesh, batch_like)
    c_sh = sh.cache_shardings(mesh, cache_like, model.cfg)
    raw_step = make_prefill(model, S_max, rt)

    def prefill(params, batch):                 # module jit_prefill
        with mesh_hint(mesh, rt.flags):
            return raw_step(params, batch)

    step = jax.jit(
        prefill,
        in_shardings=(p_sh, b_sh),
        out_shardings=(None, c_sh),
    )
    return step, p_sh, b_sh, c_sh
