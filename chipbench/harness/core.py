"""What every loop shares: the run's record of host spans and counters, the
program's model config checked against the configuration file, and the
device's peak memory."""

from __future__ import annotations

import contextlib
import dataclasses
import time

import jax

from .reference import Dims
from .spec import Cell

# Configuration-file keys and the program's ModelConfig fields they must equal.
FIELDS = {
    "hidden_size": "d_model", "intermediate_size": "d_ff",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "head_dim": "hd", "num_hidden_layers": "n_layers", "vocab_size": "vocab_size",
    "padded_vocab_size": "padded_vocab", "rope_theta": "rope_theta",
    "rms_norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings",
    "torch_dtype": "dtype",
}


def program_config(conf: dict):
    """The registry's config for ``conf["arch"]`` (its smoke variant where
    ``conf["smoke"]``), cut to ``num_hidden_layers``, checked key by key
    against the file: the file states the configuration as it is run."""
    from repro.configs import get_config, get_smoke

    cfg = get_smoke(conf["arch"]) if conf.get("smoke") else get_config(conf["arch"])
    if conf["num_hidden_layers"] != cfg.n_layers:
        cfg = dataclasses.replace(cfg, n_layers=conf["num_hidden_layers"],
                                  name=f"{cfg.name}-{conf['num_hidden_layers']}l")
    for key, field in FIELDS.items():
        want = conf.get(key)
        got = getattr(cfg, field)
        if want is None or got != want:
            raise SystemExit(f"{conf['arch']}: the program has {field}={got!r}, "
                             f"the configuration file {key}={want!r}")
    for field in ("qkv_bias", "qk_norm", "sliding_window", "local_global_ratio", "n_experts"):
        if getattr(cfg, field):
            raise SystemExit(f"{conf['arch']}: {field} is set; the reference has none")
    return cfg


@dataclasses.dataclass
class Run:
    """Everything the metric readers may read."""
    cell: Cell
    dims: Dims
    peaks: dict
    trace_on: bool
    spans: dict = dataclasses.field(default_factory=dict)     # name -> [(t0, t1)]
    counters: dict = dataclasses.field(default_factory=dict)
    data: dict = dataclasses.field(default_factory=dict)      # per loop
    trace: dict | None = None                                 # trace.reduce(...)

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span; in a traced run also a ``cb:<name>`` annotation in the
        profiler's trace, on the device's clock."""
        ann = jax.profiler.TraceAnnotation("cb:" + name) if self.trace_on else None
        if ann:
            ann.__enter__()
        t0 = time.perf_counter()
        try:
            yield
        finally:
            t1 = time.perf_counter()
            if ann:
                ann.__exit__(None, None, None)
            self.spans.setdefault(name, []).append((t0, t1))


def memory_peak_bytes() -> int:
    """The peak on the fullest chip, as the runtime reports it."""
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices()))
