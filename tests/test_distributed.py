"""Multi-device distribution tests. These run in SUBPROCESSES because the
host-platform device count must be set before jax initializes (and the rest
of the suite must see 1 device)."""

import os
import subprocess
import sys
import textwrap

import pytest

REPO_SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def _run(code: str, devices: int = 8, timeout: int = 420) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = REPO_SRC
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, env=env, timeout=timeout,
    )
    assert out.returncode == 0, f"stdout:\n{out.stdout}\nstderr:\n{out.stderr}"
    return out.stdout


def test_sharded_train_step_matches_single_device():
    """The 4x2 sharded train step computes the same loss trajectory as the
    unsharded one (same model, same batch)."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs import get_smoke
        from repro.models import build_model
        from repro.runtime import RuntimeConfig, make_train_state, jit_train_step, make_train_step
        from repro.launch.mesh import make_host_mesh

        cfg = get_smoke("phi4-mini-3.8b")
        model = build_model(cfg)
        rt = RuntimeConfig(remat=None, zero1=True, accum=2)
        state = make_train_state(model, jax.random.PRNGKey(0), rt)
        batch = {
            "tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0, cfg.vocab_size),
            "labels": jax.random.randint(jax.random.PRNGKey(2), (8, 32), 0, cfg.vocab_size),
        }
        # single-device reference
        ref_step = jax.jit(make_train_step(model, rt))
        ref_state, ref_m = ref_step(state, batch)

        mesh = make_host_mesh(4, 2)
        state2 = make_train_state(model, jax.random.PRNGKey(0), rt)
        step, st_sh, b_sh = jit_train_step(model, mesh, rt, state2, batch)
        state2 = jax.device_put(state2, st_sh)
        jbatch = jax.device_put(batch, b_sh)
        new_state, m = step(state2, jbatch)
        a, b = float(ref_m["loss"]), float(m["loss"])
        assert abs(a - b) / abs(a) < 2e-3, (a, b)
        print("OK", a, b)
    """)


def test_decode_step_sharded_cache():
    _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke
        from repro.models import build_model
        from repro.runtime import RuntimeConfig, jit_decode_step
        from repro.launch.mesh import make_host_mesh

        cfg = get_smoke("qwen3-14b")
        model = build_model(cfg)
        rt = RuntimeConfig()
        params = model.init(jax.random.PRNGKey(0))
        cache = model.init_cache(8, 64)
        batch = {"token": jnp.ones((8,), jnp.int32)}
        mesh = make_host_mesh(2, 4)
        step, p_sh, c_sh, b_sh = jit_decode_step(model, mesh, rt, params, cache, batch)
        params = jax.device_put(params, p_sh)
        cache = jax.device_put(cache, c_sh)
        batch = jax.device_put(batch, b_sh)
        logits, cache = step(params, cache, batch)
        assert logits.shape == (8, cfg.padded_vocab)
        assert int(cache["pos"]) == 1
        # one more step re-uses the donated cache
        logits, cache = step(params, cache, {"token": jnp.zeros((8,), jnp.int32)})
        assert int(cache["pos"]) == 2
        print("OK")
    """)


def test_cache_shardings_follow_the_stack_layout():
    """Every family's k/v stacks shard batch over data and sequence over
    model wherever kv_order_for(hd) puts them: (L, B, K, hd, S) for the smoke
    configs' heads of 32, (L, B, K, S, hd) for heads of 128. Zamba2's stacks
    hold one entry per invocation site of its shared block."""
    _run("""
        import dataclasses
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_smoke
        from repro.models import build_model
        from repro.runtime import cache_shardings
        from repro.launch.mesh import make_host_mesh

        mesh = make_host_mesh(2, 4)
        wide = dataclasses.replace(get_smoke("qwen3-14b"), head_dim=128)
        for cfg, want in [
            (get_smoke("qwen3-14b"), {"k": P(None, "data", None, None, "model")}),
            (get_smoke("internvl2-2b"), {"v": P(None, "data", None, None, "model")}),
            (wide, {"k": P(None, "data", None, "model", None)}),
            (get_smoke("zamba2-7b"), {"k": P(None, "data", None, None, "model")}),
            (get_smoke("whisper-tiny"), {"v": P(None, "data", None, None, "model"),
                                         "ck": P(None, "data", "model", None, None)}),
        ]:
            model = build_model(cfg)
            cache = jax.eval_shape(lambda: model.init_cache(8, 64))
            sh = cache_shardings(mesh, cache, model.cfg)
            for name, spec in want.items():
                assert sh[name].spec == spec, (cfg.name, name, sh[name].spec)
        print("OK")
    """)


def test_granite_h_serves_on_a_mesh():
    """granite-4.0-h's stacks shard batch over data (heads over model where
    they divide), and prefill plus decode on a (2, 4) mesh give the
    one-device logits."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.configs import get_smoke
        from repro.models import build_model
        from repro.runtime import RuntimeConfig, cache_shardings, jit_decode_step, jit_prefill
        from repro.launch.mesh import make_host_mesh

        model = build_model(get_smoke("granite-4.0-h-micro"))
        B, S, S_max = 8, 24, 64
        cache = jax.eval_shape(lambda: model.init_cache(B, S_max))
        sh = cache_shardings(make_host_mesh(2, 4), cache, model.cfg)
        assert sh["h"].spec == P(None, "data", "model", None, None), sh["h"].spec
        assert sh["conv"].spec == P(None, None, "data", None), sh["conv"].spec
        assert sh["k"].spec == P(None, "data", None, None, None), sh["k"].spec

        params = model.init(jax.random.PRNGKey(0))
        toks = jax.random.randint(jax.random.PRNGKey(1), (B, S + 3), 0, model.cfg.vocab_size)
        batch = {"tokens": toks[:, :S]}
        cache_like = jax.eval_shape(lambda p, b: model.prefill(p, b, S_max), params, batch)[1]
        tok_like = {"token": jax.ShapeDtypeStruct((B,), jnp.int32)}
        outs = []
        for dp, tp in ((1, 1), (2, 4)):
            mesh = make_host_mesh(dp, tp)
            pre, p_sh, b_sh, _ = jit_prefill(model, mesh, RuntimeConfig(), S_max, params,
                                             batch, cache_like)
            dec, *_ = jit_decode_step(model, mesh, RuntimeConfig(), params, cache_like,
                                      tok_like)
            p = jax.device_put(params, p_sh)
            logits, c = pre(p, jax.device_put(batch, b_sh))
            got = [logits]
            for t in range(3):
                logits, c = dec(p, c, {"token": toks[:, S + t]})
                got.append(logits)
            outs.append(np.stack([np.asarray(g) for g in got]))
        err = np.max(np.abs(outs[0] - outs[1])) / np.max(np.abs(outs[0]))
        assert err < 1e-4, err
        print("OK")
    """)


def test_dryrun_cell_small_mesh_moe():
    """MoE lowering + compile + roofline extraction on a small mesh —
    the dry-run machinery itself, in miniature."""
    _run("""
        import jax, jax.numpy as jnp
        from repro.configs import get_smoke, TRAIN_4K
        import dataclasses
        from repro.models import build_model
        from repro.runtime import RuntimeConfig, make_train_state, jit_train_step
        from repro.runtime.costs import hlo_collective_bytes, jaxpr_costs
        from repro.runtime.parallel import make_train_step
        from repro.launch.mesh import make_host_mesh

        cfg = get_smoke("qwen3-moe-30b-a3b")
        model = build_model(cfg)
        rt = RuntimeConfig(accum=2)
        mesh = make_host_mesh(2, 4)
        rng_sds = jax.ShapeDtypeStruct((2,), jnp.uint32)
        state_sds = jax.eval_shape(lambda r: make_train_state(model, r, rt), rng_sds)
        specs = {
            "tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
            "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32),
        }
        step, *_ = jit_train_step(model, mesh, rt, state_sds, specs)
        lowered = step.lower(state_sds, specs)
        compiled = lowered.compile()
        mem = compiled.memory_analysis()
        coll = hlo_collective_bytes(compiled.as_text())
        alg = jaxpr_costs(jax.make_jaxpr(make_train_step(model, rt))(state_sds, specs))
        assert alg["flops"] > 0
        assert coll["count"] > 0            # EP dispatch produced collectives
        assert mem.temp_size_in_bytes > 0
        print("OK flops", alg["flops"], "coll", coll["count"])
    """)


@pytest.mark.slow
def test_production_mesh_shapes():
    _run("""
        from repro.launch.mesh import make_production_mesh
        m1 = make_production_mesh()
        assert dict(m1.shape) == {"data": 16, "model": 16}
        m2 = make_production_mesh(multi_pod=True)
        assert dict(m2.shape) == {"pod": 2, "data": 16, "model": 16}
        assert m2.devices.size == 512
        print("OK")
    """, devices=512)


def test_int8_allreduce_shard_map():
    """The collective that plain quantize->dequantize cannot buy under GSPMD
    (EXPERIMENTS §Perf A2/B4): int8 wire payloads via shard_map, ~1% error,
    s8 all-to-all/all-gather verified in the compiled HLO."""
    _run("""
        import jax, jax.numpy as jnp, re
        from jax.sharding import PartitionSpec as P
        from jax.experimental.shard_map import shard_map
        from repro.optim.compression import int8_allreduce

        mesh = jax.make_mesh((8,), ("data",),
                             axis_types=(jax.sharding.AxisType.Auto,))
        x = jax.random.normal(jax.random.PRNGKey(0), (8, 37, 5))

        def f(xl):
            return int8_allreduce(xl[0], "data")[None]

        g = shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data"))
        out = g(x)
        want = jnp.mean(x, axis=0)
        rel = float(jnp.max(jnp.abs(out[0] - want))) / float(jnp.max(jnp.abs(want)))
        assert rel < 0.05, rel
        hlo = jax.jit(g).lower(x).compile().as_text()
        s8 = [l for l in hlo.splitlines()
              if re.search(r"= s8.*(all-to-all|all-gather)", l)]
        assert len(s8) >= 2, "int8 payloads not on the wire"
        print("OK", rel)
    """)
