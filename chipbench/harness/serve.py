"""The serving loop: the program's jitted prefill and decode step, driven
by closed-loop clients in static waves.

The program serves one static batch at a time (one cache position for the
whole batch), so a wave starts when the previous one ends, with every slot
holding a request; a client sends its next request when the last token of
its previous one reaches the host. Tokens are greedy. One decode step is kept
in flight ahead of the host, which reads each token as it arrives.
"""

from __future__ import annotations

import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from . import reference as R
from .core import Run, memory_peak_bytes, program_config
from .traffic import ServeTraffic
from .weights import make_params, seed_key


@jax.jit
def pick(logits):
    """Greedy: the best token of each row."""
    return jnp.argmax(logits, axis=-1).astype(jnp.int32)


class Server:
    """Set-up: the program's jit_prefill and jit_decode_step at this cell's
    shapes, with weights made on the device from the seed, and every shape
    warmed up."""

    def __init__(self, run: Run, seed: int):
        from repro.launch.mesh import make_host_mesh
        from repro.launch.serve import cache_len
        from repro.models import build_model
        from repro.runtime import RuntimeConfig, jit_decode_step, jit_prefill

        conf = run.cell.config
        self.run = run
        model = build_model(program_config(conf))
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
            logits, cache = self.decode(self.params, cache, {"token": pick(logits)})
        np.asarray(pick(logits))
        del logits, cache

    def serve_wave(self, wave, deadline: float):
        """Returns the host arrival time of each step's tokens and the tokens
        (G, B), G being the longest output the wave asks for, or fewer where
        the window closes first: no step is started after ``deadline``."""
        run = self.run
        G = max(wave.outputs)
        times, toks = [], []
        with run.span("prefill"):
            logits, cache = self.prefill(
                self.params, jax.device_put({"tokens": wave.prompts}, self.b_sh))
            ahead = pick(logits)
            if G > 1:
                logits, cache = self.decode(self.params, cache, {"token": ahead})
            toks.append(np.asarray(ahead))
            times.append(time.perf_counter())
        for j in range(1, G):
            if times[-1] >= deadline:
                break
            with run.span("decode_step"):
                cur = pick(logits)
                if j + 1 < G:
                    logits, cache = self.decode(self.params, cache, {"token": cur})
                toks.append(np.asarray(cur))
                times.append(time.perf_counter())
        jax.block_until_ready(logits)
        del logits, cache
        return times, np.stack(toks)

    def free(self):
        del self.params
        gc.collect()


def drive(run: Run, seed: int, seconds: float) -> dict:
    """Set-up, the measured window, then the check against the reference.

    The window lasts ``seconds``: waves start until it closes, and the wave
    open at its close stops there. A token counts where it reached the host
    inside the window; a request is finished where all of its did."""
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


def sample_requests(seed: int, reqs, n: int):
    """The longest finished request and ``n - 1`` others drawn from the seed."""
    longest = max(range(len(reqs)), key=lambda i: (reqs[i][2], -i))
    rest = [i for i in range(len(reqs)) if i != longest]
    rng = np.random.default_rng([seed & 0xFFFFFFFF, (seed >> 32) & 0xFFFFFFFF, 7])
    pick_ = rng.choice(len(rest), size=min(n - 1, len(rest)), replace=False)
    return [longest] + [rest[i] for i in sorted(pick_)]


def reference_inputs(served, reqs, idx, P: int, T: int, L: int):
    """Token rows (prompt, then the served tokens but the last) of length T;
    the served tokens as targets (N, L) at the positions P - 1 ... that
    predicted them; and their mask."""
    N = len(idx)
    tokens = np.zeros((N, T), np.int32)
    targets = np.zeros((N, L), np.int32)
    valid = np.zeros((N, L), bool)
    for r, i in enumerate(idx):
        w, b, n = reqs[i][:3]
        prompts, toks, _ = served[w]
        out = toks[:n, b]
        tokens[r, :P] = prompts[b]
        tokens[r, P:P + n - 1] = out[:-1]
        targets[r, :n] = out
        valid[r, :n] = True
    return tokens, targets, valid


def check_served(run: Run, seed: int, served, reqs) -> dict:
    """The widest gap by which a served token's logit lies below the plain
    float32 reference's best, over a sample of finished requests."""
    tr = run.cell.traffic
    P, L = int(tr["prompt_tokens"]), int(tr["output_tokens"]["hi"])
    T = -(-(P + L) // 512) * 512
    idx = sample_requests(seed, reqs, int(tr["check_requests"]))
    tokens, targets, valid = reference_inputs(served, reqs, idx, P, T, L)
    run.data["check_inputs"] = (tokens, targets, valid, P - 1)
    with jax.default_matmul_precision("highest"):
        gaps = R.served_gaps(seed_key(seed), run.dims, jnp.asarray(tokens),
                             jnp.asarray(targets), jnp.asarray(valid), P - 1)
    run.counters["checked_tokens"] = int(valid.sum())
    return {"logit_gap": (float(np.max(np.asarray(gaps))), float(run.cell.limits["logit_gap"]))}


def control(run: Run, seed: int) -> dict:
    """The control, judged as the program is: the reference one precision
    down (fp8) in the program's place, read at every position of the prompts
    and served tokens that the check read, as the gap of the token the fp8
    model puts first."""
    tokens, targets, valid, start = run.data["check_inputs"]
    with jax.default_matmul_precision("highest"):
        gaps = R.served_gaps(seed_key(seed), run.dims, jnp.asarray(tokens),
                             jnp.asarray(targets), jnp.asarray(valid), start, quant="fp8")
    return {"logit_gap": (float(np.max(np.asarray(gaps))), float(run.cell.limits["logit_gap"]))}
