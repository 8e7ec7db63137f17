"""What ``jax.checkpoint`` keeps at the attention core (``models/transformer._remat``,
``ops/pallas/flash_attention``'s two names): the flash kernel's output and row statistics,
so that its forward runs once a layer a step and not twice. On the three structures that
checkpoint a softmax layer — the scanned layer (``_make_stage_fn``), a declared pattern's
mixers (``remat_parts``: window + global layers, latent layers) and the
multi-token-prediction module — at tiny widths on the CPU, the kernel interpreted
(``attention_impl`` "flash": on a CPU "auto" never takes it).

The tolerance of the gradients is ``tests/test_parallel.py::TestRematPolicy``'s: the loss
to 1e-6 of itself, every gradient leaf to 1e-5."""

import collections
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax._src.ad_checkpoint import saved_residuals
from jax.extend.core import Literal

from tests import test_mla_rope_mtp as latent
from tests import test_window_gqa as banded
from torchft_tpu.models import transformer as T
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn
from torchft_tpu.ops.pallas.flash_attention import CORE_LSE, CORE_OUT
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh

BATCH, SEQ = 2, 128
# name -> (the configuration's sizes, (query heads, value width) of each softmax layer: each calls the kernel once a step)
STRUCTURES = {
    "scanned": (dict(vocab_size=64, d_model=32, n_layers=3, n_heads=2, head_dim=16, d_ff=64), [(2, 16)] * 3),
    # a dense global layer, then a scanned period of three window layers and a global one
    "window_global": (banded.STACK, [(6, 16), (8, 16), (8, 16), (8, 16), (6, 16)]),
    "latent": (latent.SIZES["experts"], [(2, 8)] * 2),  # a dense and a sparse latent layer, unrolled
    "module": (latent.SIZES["module"], [(2, 8)] * 4),  # a dense layer, ONE sparse layer scanned twice, the module's layer
}


def make(name, **changes):
    sizes = {"dtype": jnp.float32, "attention_impl": "flash", **STRUCTURES[name][0], **changes}
    cfg = TransformerConfig(**sizes)
    params = init_params(jax.random.PRNGKey(3), cfg)
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 64, (BATCH, SEQ)), jnp.int32)
    return cfg, params, tokens


def without_the_names(monkeypatch):
    """``_remat`` as the parent had it: policy "all" hands ``jax.checkpoint`` None."""
    monkeypatch.setattr(T, "_remat_policy", lambda name: None)


def kernel_calls(jaxpr, times=1, out=None):
    """``pallas_call`` and ``checkpoint_name`` equations by their name, each
    counted as often as the ``lax.scan``s around it run their bodies."""
    out = collections.Counter() if out is None else out
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("pallas_call", "name"):
            out[eqn.params["name"]] += times
        inner = times * eqn.params["length"] if eqn.primitive.name == "scan" else times
        for sub in banded.sub_jaxprs(eqn):
            kernel_calls(sub, inner, out)
    return out


def program(cfg, params, tokens):
    return jax.make_jaxpr(jax.value_and_grad(lambda p, t: loss_fn(p, t, cfg)))(params, tokens)


def spelled_out(jaxpr, out=None):
    """A jaxpr equation by equation, sub-jaxprs in place, variables numbered
    by first use within their jaxpr, a checkpoint's ``policy`` left out: the
    printed text also depends on which sub-jaxprs are one object (it hoists
    those), which a second trace in one process changes."""
    out = [] if out is None else out
    ids = {}
    var = lambda v: repr(v.val) if isinstance(v, Literal) else ids.setdefault(v, len(ids))
    out.append([var(v) for v in (*jaxpr.constvars, *jaxpr.invars)])
    for eqn in jaxpr.eqns:
        for sub in banded.sub_jaxprs(eqn):
            spelled_out(sub, out)
        params = [
            (key, re.sub(r"0x[0-9a-f]+", "0x", str(value))) for key, value in sorted(eqn.params.items())
            if key != "policy" and not banded.jaxprs_in(value)
        ]
        outs = [(var(v), str(v.aval)) for v in eqn.outvars]
        out.append((eqn.primitive.name, [var(v) for v in eqn.invars], outs, params))
    out.append([var(v) for v in jaxpr.outvars])
    return out


@pytest.mark.parametrize("name", list(STRUCTURES))
def test_the_kernels_forward_runs_once_a_softmax_layer(name, monkeypatch):
    cfg, params, tokens = make(name)
    layers = len(STRUCTURES[name][1])
    for policy in ("all", "dots"):
        calls = kernel_calls(program(dataclasses.replace(cfg, remat_policy=policy), params, tokens).jaxpr)
        assert (calls["flash_fwd"], calls["flash_bwd"]) == (layers, layers), (policy, calls)
        assert (calls[CORE_OUT], calls[CORE_LSE]) == (layers, layers)
    without_the_names(monkeypatch)  # the parent's program: the forward again in every checkpoint's recomputation
    calls = kernel_calls(program(cfg, params, tokens).jaxpr)
    assert (calls["flash_fwd"], calls["flash_bwd"]) == (2 * layers, layers)


@pytest.mark.parametrize("axes", [{}, {"dp": 2, "tp": 2}], ids=["one_device", "dp2_tp2"])
def test_the_kernel_inside_a_shard_map_runs_once_too(axes):
    """Under a mesh the kernel's call sits in a ``shard_map`` (``_flash_sharded``:
    every benchmark cell, over its one chip): the names are seen through it."""
    cfg, params, tokens = make("scanned")
    mesh = make_mesh(MeshConfig(**axes), devices=jax.devices()[: MeshConfig(**axes).total])
    with jax.set_mesh(mesh):
        jaxpr = jax.make_jaxpr(jax.value_and_grad(lambda p, t: loss_fn(p, t, cfg, mesh)))(params, tokens).jaxpr
    calls = kernel_calls(jaxpr)
    assert "shard_map" in str(jaxpr) and (calls["flash_fwd"], calls["flash_bwd"]) == (3, 3)


def residuals(cfg, params, tokens):
    """(shape, dtype) -> how many, of everything the backward of ``loss_fn``
    keeps from its forward (``print_saved_residuals``'s list)."""
    kept = saved_residuals(lambda p: loss_fn(p, tokens, cfg), params)
    return collections.Counter((aval.shape, str(aval.dtype)) for aval, _ in kept)


@pytest.mark.parametrize("name", list(STRUCTURES))
def test_the_two_named_arrays_are_kept_and_no_other_new_residual(name, monkeypatch):
    cfg, params, tokens = make(name)
    avals = residuals(cfg, params, tokens)
    without_the_names(monkeypatch)
    before = residuals(cfg, params, tokens)
    assert not before - avals  # everything the parent kept is kept
    new = avals - before
    # a call's output [B, H, S, Dv] and its row statistics [B, H, 8, S] (the kernel's _ROWS layout), stacked under a scan
    size = lambda shape: int(np.prod(shape))
    assert sum(n * size(shape) for (shape, _), n in new.items()) == sum(
        BATCH * h * SEQ * dv + BATCH * h * 8 * SEQ for h, dv in STRUCTURES[name][1]
    )
    assert all(shape[-1] == SEQ or shape[-2] == SEQ for shape, _ in new)


@pytest.mark.parametrize("name", list(STRUCTURES))
def test_gradients_are_those_without_remat_and_those_of_dots(name):
    cfg, params, tokens = make(name)
    out = {}
    for key, changes in {"all": {}, "off": {"remat": False}, "dots": {"remat_policy": "dots"}}.items():
        c = dataclasses.replace(cfg, **changes)
        out[key] = jax.jit(jax.value_and_grad(lambda p, t, c=c: loss_fn(p, t, c)))(params, tokens)
    for other in ("off", "dots"):
        assert float(out["all"][0]) == pytest.approx(float(out[other][0]), rel=1e-6)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-5),
            out["all"][1], out[other][1],
        )


@pytest.mark.parametrize("name", list(STRUCTURES))
def test_a_path_without_the_kernel_traces_to_the_parents_program(name, monkeypatch):
    """``chunked_attention`` names nothing: but for the policy handed to
    ``jax.checkpoint``, the program is the one policy None traced."""
    cfg, params, tokens = make(name, attention_impl="chunked")
    now = program(cfg, params, tokens).jaxpr
    calls = kernel_calls(now)
    assert not {CORE_OUT, CORE_LSE, "flash_fwd", "flash_bwd"} & set(calls)
    without_the_names(monkeypatch)
    assert spelled_out(now) == spelled_out(program(cfg, params, tokens).jaxpr)
