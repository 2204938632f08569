"""The serve step's named scopes: module names, the scope vocabulary in the
compiled program's ``op_name`` metadata (the dense model's, and the Mamba2
mixer's names in granite-4.0-h's), and proof that the scopes change nothing
else in the compiled program."""

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
# granite-4.0-h adds the mixer's names
HYBRID = "granite-4.0-h-micro"
SSM_PREFILL = PREFILL | {"kv_write", "ssm_proj", "ssm_conv", "ssm_scan"}
SSM_DECODE = DECODE | {"ssm_proj", "ssm_conv", "ssm_state"}
# Source-location tables that open the HLO text; they name the caller's lines.
TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")


def _compiled_texts(arch="phi4-mini-3.8b"):
    """The compiled HLO text of a smoke config's prefill and decode."""
    model = build_model(get_smoke(arch))
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


@pytest.fixture(scope="module")
def hybrid_texts():
    return _compiled_texts(HYBRID)


def test_module_names(texts):
    assert texts["prefill"].startswith("HloModule jit_prefill,")
    assert texts["decode_step"].startswith("HloModule jit_decode_step,")


@pytest.mark.parametrize("step,names", [("prefill", PREFILL), ("decode_step", DECODE)])
def test_vocabulary_in_op_names(texts, step, names):
    paths = re.findall(r'op_name="([^"]*)"', texts[step])
    assert all(p.startswith(f"jit({step})/") for p in paths if p.startswith("jit("))
    seen = {part for p in paths for part in p.split("/")}
    assert names <= seen, names - seen


@pytest.mark.parametrize("step,names", [("prefill", SSM_PREFILL),
                                        ("decode_step", SSM_DECODE)])
def test_mixer_names_in_op_names(hybrid_texts, step, names):
    paths = re.findall(r'op_name="([^"]*)"', hybrid_texts[step])
    seen = {part for p in paths for part in p.split("/")}
    assert names <= seen, names - seen


def test_scopes_change_nothing_but_metadata(texts, monkeypatch):
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _compiled_texts()
    for step, text in texts.items():
        assert "metadata=" in text
        assert _renumbered(_without_metadata(bare[step])) == \
            _renumbered(_without_metadata(text)), step
        assert "/layers/" not in bare[step]


def _renumbered(text: str) -> str:
    """Instruction names numbered in their order of appearance: the compiler
    numbers the transposes of a SEQ_MINOR cache in the order it meets their
    scopes."""
    names = {}
    return re.sub(r"%([\w\-]+?)\.(\d+)\b",
                  lambda m: "%" + m.group(1) + "." + names.setdefault(m.group(0), str(len(names))),
                  text)


def test_mixer_scopes_change_nothing_but_metadata(hybrid_texts, monkeypatch):
    monkeypatch.setattr(jax, "named_scope", lambda name: contextlib.nullcontext())
    bare = _compiled_texts(HYBRID)
    for step, text in hybrid_texts.items():
        assert "/ssm_" in text and "/ssm_" not in bare[step]
        assert _renumbered(_without_metadata(bare[step])) == \
            _renumbered(_without_metadata(text)), step
