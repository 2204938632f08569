"""The serving loop of ``serve.py`` for a Mamba2 hybrid (Granite-4.0-H style):
the same waves, spans (``prefill``, ``decode_step``, ``window``), end-to-end
metrics and counters, with the hybrid's weights (``weights_hybrid.py``) and
its plain reference (``reference_hybrid.py``) deciding ``correct``.

The program's config is checked against the configuration file: the dense
keys as ``core.program_config`` checks them, and ``HYBRID_FIELDS`` beside
them.
"""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from . import reference_hybrid as RH
from . import serve
from .core import Run, memory_peak_bytes, program_config
from .traffic import ServeTraffic
from .weights import seed_key
from .weights_hybrid import make_params

# Configuration-file keys and the program's ModelConfig fields they must equal.
HYBRID_FIELDS = {
    "layer_types": "layer_types", "mamba_d_state": "ssm_state",
    "mamba_d_head": "ssm_head_dim", "mamba_n_heads": "n_ssm_heads",
    "mamba_expand": "ssm_expand", "mamba_d_conv": "ssm_conv_width",
    "mamba_chunk_size": "ssm_chunk", "embedding_multiplier": "embedding_multiplier",
    "residual_multiplier": "residual_multiplier",
    "attention_multiplier": "attention_multiplier", "logits_scaling": "logits_scaling",
}


def hybrid_config(conf: dict):
    """``core.program_config`` with the hybrid's keys checked too."""
    cfg = program_config(conf)
    for key, field in HYBRID_FIELDS.items():
        want, got = conf.get(key), getattr(cfg, field)
        if key == "layer_types":
            want, got = tuple(want or ()), tuple(got)
        if want is None or got != want:
            raise SystemExit(f"{conf['arch']}: the program has {field}={got!r}, "
                             f"the configuration file {key}={want!r}")
    if cfg.nope != (conf.get("position_embedding_type") == "nope"):
        raise SystemExit(f"{conf['arch']}: the program's nope={cfg.nope!r}, the file's "
                         f"position_embedding_type={conf.get('position_embedding_type')!r}")
    return cfg


class Server(serve.Server):
    """``serve.Server`` with the hybrid's config check and weights."""

    def __init__(self, run: Run, seed: int):
        from repro.launch.mesh import make_host_mesh
        from repro.launch.serve import cache_len
        from repro.models import build_model
        from repro.runtime import RuntimeConfig, jit_decode_step, jit_prefill

        conf = run.cell.config
        self.run = run
        model = build_model(hybrid_config(conf))
        rt = RuntimeConfig()
        mesh = make_host_mesh(1, 1)
        self.traffic = ServeTraffic(run.cell.traffic, seed, conf["vocab_size"])
        B, P = self.traffic.B, self.traffic.P
        self.S_max = cache_len(P + self.traffic.max_output)
        key = seed_key(seed)
        params_like = jax.eval_shape(model.init, key)
        batch_like = {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)}
        cache_like = jax.eval_shape(
            lambda p, b: model.prefill(p, b, self.S_max), params_like, batch_like)[1]
        tok_like = {"token": jax.ShapeDtypeStruct((B,), jnp.int32)}
        self.prefill, p_sh, self.b_sh, _ = jit_prefill(
            model, mesh, rt, self.S_max, params_like, batch_like, cache_like)
        self.decode, *_ = jit_decode_step(
            model, mesh, rt, params_like, cache_like, tok_like)
        self.params = jax.jit(
            lambda k: make_params(k, params_like, conf["vocab_size"], conf["torch_dtype"]),
            out_shardings=p_sh)(key)
        # warm up: one prefill and two decode steps at the window's shapes
        wave = self.traffic.wave(0)
        logits, cache = self.prefill(self.params, jax.device_put({"tokens": wave.prompts}, self.b_sh))
        for _ in range(2):
            logits, cache = self.decode(self.params, cache, {"token": serve.pick(logits)})
        np.asarray(serve.pick(logits))
        del logits, cache


def drive(run: Run, seed: int, seconds: float) -> dict:
    """As ``serve.drive``: set-up, the measured window, then the check
    against the hybrid's reference."""
    t0 = time.perf_counter()
    srv = Server(run, seed)
    setup_s = time.perf_counter() - t0
    tr = srv.traffic
    B, P = tr.B, tr.P

    reqs = []                      # (wave, slot, n_out, ttft_s, gaps_s, finished)
    served = []                    # per wave: (prompts, tokens (G, B), outputs)
    if run.trace_on:
        jax.profiler.start_trace(run.data["trace_dir"])
    with run.span("window"):
        start = time.perf_counter()
        end = start + seconds
        sent = [start] * B
        w = 0
        while time.perf_counter() < end:
            wave = tr.wave(w)
            times, toks = srv.serve_wave(wave, end)
            for b, n in enumerate(wave.outputs):
                got = [t for t in times[:n] if t <= end]
                if not got:
                    continue
                reqs.append((w, b, n, got[0] - sent[b], np.diff(got).tolist(), len(got) == n))
                if len(got) == n:
                    sent[b] = got[-1]
            served.append((wave.prompts, toks, wave.outputs))
            w += 1
    if run.trace_on:
        jax.profiler.stop_trace()
    peak = memory_peak_bytes()
    srv.free()

    out_tokens = sum(len(r[4]) + 1 for r in reqs)
    ttft = [r[3] for r in reqs]
    gaps = [g for r in reqs for g in r[4]]
    done = [r for r in reqs if r[5]]
    run.counters.update(waves=w, requests=len(reqs), finished=len(done),
                        output_tokens=out_tokens,
                        decode_steps=len(run.spans.get("decode_step", ())))
    run.data.update(B=B, P=P, S_max=srv.S_max,
                    wave_steps=[toks.shape[0] for _, toks, _ in served])
    e2e = {
        "output_tokens_per_s": out_tokens / seconds,
        "ttft_p95_ms": float(np.percentile(ttft, 95)) * 1e3,
        "tpot_p95_ms": float(np.percentile(gaps, 95)) * 1e3 if gaps else None,
        "setup_s": setup_s,
    }
    run.data["e2e"] = e2e
    t1 = time.perf_counter()
    checks = check_served(run, seed, served, done)
    run.counters["check_s"] = time.perf_counter() - t1
    return {"e2e": e2e, "attempted": w * B, "failed": 0, "window_s": seconds,
            "memory_peak_bytes": peak, "checks": checks}


def check_served(run: Run, seed: int, served, reqs) -> dict:
    """The widest gap by which a served token's logit lies below the plain
    float32 reference's best, over a sample of finished requests."""
    tr = run.cell.traffic
    P, L = int(tr["prompt_tokens"]), int(tr["output_tokens"]["hi"])
    T = -(-(P + L) // 512) * 512
    idx = serve.sample_requests(seed, reqs, int(tr["check_requests"]))
    tokens, targets, valid = serve.reference_inputs(served, reqs, idx, P, T, L)
    run.data["check_inputs"] = (tokens, targets, valid, P - 1)
    with jax.default_matmul_precision("highest"):
        gaps = RH.served_gaps(seed_key(seed), RH.HDims.of(run.cell.config), jnp.asarray(tokens),
                              jnp.asarray(targets), jnp.asarray(valid), P - 1)
    run.counters["checked_tokens"] = int(valid.sum())
    return {"logit_gap": (float(np.max(np.asarray(gaps))), float(run.cell.limits["logit_gap"]))}


def control(run: Run, seed: int) -> dict:
    """The control, judged as the program is: the reference one precision
    down (fp8) in the program's place, read on the inputs the check read."""
    tokens, targets, valid, start = run.data["check_inputs"]
    with jax.default_matmul_precision("highest"):
        gaps = RH.served_gaps(seed_key(seed), RH.HDims.of(run.cell.config), jnp.asarray(tokens),
                              jnp.asarray(targets), jnp.asarray(valid), start, quant="fp8")
    return {"logit_gap": (float(np.max(np.asarray(gaps))), float(run.cell.limits["logit_gap"]))}
