"""The serve step's named scopes: module names, the scope vocabulary in the
compiled program's ``op_name`` metadata, and proof that the scopes change
nothing else in the compiled program."""

import contextlib
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_smoke
from repro.launch.mesh import make_host_mesh
from repro.models import build_model
from repro.runtime import RuntimeConfig, jit_decode_step, jit_prefill

# The names each step uses on the dense jnp path.
PREFILL = {"embed", "layers", "norm", "attn_proj", "attend", "mlp", "lm_head"}
DECODE = PREFILL | {"kv_write"}
# Source-location tables that open the HLO text; they name the caller's lines.
TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _compiled_texts():
    """The compiled HLO text of the smoke dense config's prefill and decode."""
    model = build_model(get_smoke("phi4-mini-3.8b"))
    rt, mesh = RuntimeConfig(), make_host_mesh(1, 1)
    B, P, S_max = 2, 16, 64
    params = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    batch = {"tokens": jax.ShapeDtypeStruct((B, P), jnp.int32)}
    cache = jax.eval_shape(lambda p, b: model.prefill(p, b, S_max), params, batch)[1]
    tok = {"token": jax.ShapeDtypeStruct((B,), jnp.int32)}
    prefill, *_ = jit_prefill(model, mesh, rt, S_max, params, batch, cache)
    decode, *_ = jit_decode_step(model, mesh, rt, params, cache, tok)
    return {"prefill": prefill.lower(params, batch).compile().as_text(),
            "decode_step": decode.lower(params, cache, tok).compile().as_text()}


def _without_metadata(text: str) -> str:
    text = re.sub(r", metadata=\{[^}]*\}", "", text)
    blocks = text.split("\n\n")
    return "\n\n".join(b for b in blocks if b.split("\n", 1)[0] not in TABLES)


@pytest.fixture(scope="module")
def texts():
    return _compiled_texts()


def test_module_names(texts):
    assert texts["prefill"].startswith("HloModule jit_prefill,")
    assert texts["decode_step"].startswith("HloModule jit_decode_step,")


@pytest.mark.parametrize("step,names", [("prefill", PREFILL), ("decode_step", DECODE)])
def test_vocabulary_in_op_names(texts, step, names):
    paths = re.findall(r'op_name="([^"]*)"', texts[step])
    assert all(p.startswith(f"jit({step})/") for p in paths if p.startswith("jit("))
    seen = {part for p in paths for part in p.split("/")}
    assert names <= seen, names - seen


def test_scopes_change_nothing_but_metadata(texts, monkeypatch):
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _compiled_texts()
    for step, text in texts.items():
        assert "metadata=" in text
        assert _without_metadata(bare[step]) == _without_metadata(text), step
        assert "/layers/" not in bare[step]
