"""Runtime: sharding rules, fault tolerance, restart planning."""

import time

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.runtime import HeartbeatMonitor, plan_restart
from repro.runtime.costs import hlo_collective_bytes, jaxpr_costs
from repro.runtime.sharding import _sanitize, param_spec


class FakeMesh:
    """Shape-only stand-in (sharding rules only read mesh.shape/axis_names)."""

    def __init__(self, shape: dict):
        self.shape = shape
        self.axis_names = tuple(shape)


MESH = FakeMesh({"data": 16, "model": 16})
MESH3 = FakeMesh({"pod": 2, "data": 16, "model": 16})


class TestParamRules:
    def test_ffn_megatron_pattern(self):
        assert param_spec(MESH, "layers/mlp/gate/w", (32, 4096, 16384)) == \
            P(None, None, "model")
        assert param_spec(MESH, "layers/mlp/down/w", (32, 16384, 4096)) == \
            P(None, "model", None)

    def test_vocab_sharding_with_fallback(self):
        assert param_spec(MESH, "embed/w", (152064, 5120)) == P("model", None)
        # odd vocab: model doesn't divide dim0 -> dropped
        assert param_spec(MESH, "embed/w", (92553, 2048)) == P(None, None)

    def test_moe_expert_parallel(self):
        assert param_spec(MESH, "layers/moe/gate", (48, 128, 2048, 768)) == \
            P(None, "model", None, None)

    def test_stacked_dims_padded(self):
        # zamba2's grouped mamba layers have two leading stack dims
        assert param_spec(MESH, "mamba_groups/mamba/in_proj/w", (13, 6, 3584, 14576)) == \
            P(None, None, None, "model")

    def test_norms_replicated(self):
        assert param_spec(MESH, "layers/ln1/scale", (32, 4096)) == P()

    def test_sanitize_composite_dp_prefix(self):
        # batch 32 divides (2*16) -> full composite kept
        assert _sanitize(MESH3, (("pod", "data"), None), (32, 128)) == \
            P(("pod", "data"), None)
        # batch 16 only divides data after dropping "pod"... prefix ("pod",)
        # divides 16? 16 % 2 == 0 -> ("pod",) chosen first from prefixes
        spec = _sanitize(MESH3, (("pod", "data"), None), (8, 128))
        assert spec in (P(("pod",), None), P("pod", None))

    def test_sanitize_no_axis_reuse(self):
        spec = _sanitize(MESH, ("model", "model"), (32, 32))
        assert spec == P("model", None)


class TestFault:
    def test_dead_node_detection(self):
        mon = HeartbeatMonitor(["n0", "n1"], timeout_s=10)
        now = time.monotonic()
        mon.beat("n0", now=now + 100)
        assert mon.dead_nodes(now=now + 100) == ["n1"]

    def test_straggler_detection(self):
        mon = HeartbeatMonitor([f"n{i}" for i in range(8)])
        for i in range(8):
            for _ in range(10):
                mon.beat(f"n{i}", step_time_s=1.0 if i else 5.0)
        assert mon.stragglers() == ["n0"]

    def test_no_straggler_when_uniform(self):
        mon = HeartbeatMonitor([f"n{i}" for i in range(4)])
        for i in range(4):
            for t in (1.0, 1.1, 0.9, 1.0, 1.05):
                mon.beat(f"n{i}", step_time_s=t)
        assert mon.stragglers() == []

    def test_restart_plan_shrinks_data_axis(self):
        plan = plan_restart(alive_chips=240, model_parallel=16,
                            committed_steps=[100, 200])
        assert plan.mesh_shape == (15, 16)
        assert plan.restore_step == 200

    def test_restart_plan_multipod(self):
        plan = plan_restart(alive_chips=512, model_parallel=16,
                            committed_steps=[5], pods=2)
        assert plan.mesh_shape == (2, 16, 16)

    def test_restart_plan_too_few_chips(self):
        with pytest.raises(RuntimeError):
            plan_restart(alive_chips=8, model_parallel=16, committed_steps=[])


class TestCosts:
    def test_jaxpr_dot_flops(self):
        import jax.numpy as jnp

        def f(a, b):
            return a @ b

        jx = jax.make_jaxpr(f)(jnp.zeros((64, 32)), jnp.zeros((32, 16)))
        c = jaxpr_costs(jx)
        assert c["flops"] == 2 * 64 * 32 * 16

    def test_jaxpr_scan_multiplies(self):
        import jax.numpy as jnp

        def f(x, ws):
            def body(c, w):
                return c @ w, None
            out, _ = jax.lax.scan(body, x, ws)
            return out

        jx = jax.make_jaxpr(f)(jnp.zeros((8, 8)), jnp.zeros((10, 8, 8)))
        c = jaxpr_costs(jx)
        assert c["flops"] == 10 * 2 * 8 * 8 * 8

    def test_hlo_collective_parser_trip_counts(self):
        hlo = """
%body (p: (s32[], f32[4])) -> (s32[], f32[4]) {
  %ar = f32[4]{0} all-reduce(%x), channel_id=1
}
%cond (p: (s32[], f32[4])) -> pred[] {
}
ENTRY %main (p0: f32[4]) -> f32[4] {
  %w = (s32[], f32[4]) while(%t), condition=%cond, body=%body, backend_config={"known_trip_count":{"n":"7"}}
  %ag = f32[8]{0} all-gather(%y), channel_id=2
}
"""
        c = hlo_collective_bytes(hlo)
        assert c["all-reduce"] == 7 * 16
        assert c["all-gather"] == 32
        assert c["count"] == 8
