"""The gated short convolution's stack through ``TrainStep`` and the Manager, the
names its ops carry in the lowered program, and the three cells' programs that
no other test holds to the parent's (the kinds of layer and the reference are
``tests/test_gated_conv.py``'s): a file of its own, so that these compile-heavy
tests are handed to a worker of their own in a run with several."""

import hashlib
import json
import os
import re
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tests.test_attn_core_remat import kernel_calls
from tests.test_gated_conv import ROOT, SIZES, make
from torchft_tpu.models import transformer as T
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
from torchft_tpu.parallel.train_step import TrainStep


CELLS_PROGRAMS = {
    # sha256 of the jaxpr of loss_fn's value and gradient at the cell's sizes (the chip's branch), and the first 16 of
    # the sha256 of its ``_say_once`` lines: what the parent of the PR that brought ``conv_layers``, ``qk_norm_per_head``
    # and ``router_norm_eps`` traced (b43fc8b), letter for letter. kimi-linear-1g and laguna-xs2-1g are held by
    # ``tests/test_mla_rope_mtp_train.py`` (2ca37b1f…, bd50d408…: equal at both commits too). The three programs are
    # PR 53's (the tree on top of 5cc5599): the interleaved rotation as ``x·C + swap(x)·S`` with its written gradient
    # (``ops/layers._turn_pairs``) is the one thing that moved them from 7de40b99…, 64b9511b…, 73eb8bdc…; the rotation
    # says nothing, so the ``_say_once`` digests are the parent's. joyai-flash-1g since the PR that took
    # ``jnp.take_along_axis`` out of ``_route``'s sigmoid branch (959938be…5bd7 at b50bcfa and before): ``gather`` -1 a
    # sparse layer a forward pass and ``scatter-add`` -1 a layer, ``_chosen``'s compare, select and sum over the experts
    # in their place — old counts against new in ``tests/test_gdn_train.CELLS_PROGRAMS``' comment
    # Every configuration with ``n_experts_held`` re-pinned by the PR that moves the held layer's rows by the
    # count of live ones (``ops/layers._live_rows``): a ``while`` over passes of 512 places into ``jax.lax.empty`` where a
    # gather of the window's m places stood, and ``window_live_share`` a layer — old digests and counts against new in
    # ``tests/test_gdn_train.CELLS_PROGRAMS``' comment.
    "olmo1b-1g": ((8, 2048), "73b3ad2e4c16cf95dbaa9a851fae74342c302e8e3ab0adcee3edcbad96db533a", "1bcfe2dfb3ff35a0"),
    "olmoe-1g": ((8, 2048), "65b119828cd26a22a39bc945227fb3cef92f2b8ae09109a8c17c196e5a896d2d", "1bcfe2dfb3ff35a0"),
    "joyai-flash-1g": ((2, 8192), "7a555ecf1ac27d1a22f69d07f2b4c7ba78ee1d2fba664a3111a569a1c01e6515", "a6aa64069f77d113"),
}


def cells_program(name, shape, monkeypatch):
    """(jaxpr of ``loss_fn``'s value and gradient, its ``_say_once`` lines) of a
    benchmark configuration at a cell's size, on the chip's branch."""
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        tc = json.load(f)["program"]["transformer_config"]
    cfg = TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    said = []
    monkeypatch.setattr(T, "_say_once", lambda kind, key, **fields: said.append(kind + " " + " ".join(f"{k}={v}" for k, v in fields.items())))
    # a pinned text is a fresh process's: what this process traced before (all of tests/test_gdn_train.py ahead of this
    # file in one worker, for one) changes which sub-jaxprs are one object, and the printed text hoists those
    jax.clear_caches()
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        jaxpr = jax.make_jaxpr(jax.value_and_grad(lambda p, t: loss_fn(p, t, cfg)))(params, jax.ShapeDtypeStruct(shape, jnp.int32))
    return jaxpr, said


@pytest.mark.parametrize("name", list(CELLS_PROGRAMS))
def test_the_other_cells_programs_and_what_they_say_are_unchanged(name, monkeypatch):
    shape, program, lines = CELLS_PROGRAMS[name]
    jaxpr, said = cells_program(name, shape, monkeypatch)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    assert hashlib.sha256(text.encode()).hexdigest() == program
    assert hashlib.sha256("\n".join(said).encode()).hexdigest()[:16] == lines


def test_the_cells_attention_layer_runs_the_kernel_once_and_says_so(monkeypatch):
    """`lfm2-8b-a1b-1g.fused-s8192` on the chip's branch (PR 52): the one softmax
    layer is the Pallas kernel at 64 lanes — one ``flash_fwd`` and one
    ``flash_bwd`` in the whole step, so none under ``remat``'s recomputation
    (its output and row statistics are kept) — and its one ``attention_path``
    line says which core, tile and heads."""
    from torchft_tpu.ops.pallas.flash_attention import CORE_LSE, CORE_OUT

    jaxpr, said = cells_program("lfm2-8b-a1b-1g", (2, 8192), monkeypatch)
    calls = kernel_calls(jaxpr.jaxpr)
    assert (calls["flash_fwd"], calls["flash_bwd"], calls[CORE_OUT], calls[CORE_LSE]) == (1, 1, 1, 1)
    (line,) = [text for text in said if text.startswith("attention_path ")]
    assert line.startswith("attention_path impl=flash block_q=512 block_k=512 batch=2 seq=8192 head_dim=64 value_dim=64 ")
    assert "reason=auto on a tpu: the fastest core measured at this (seq, head_dim)" in line and line.endswith("n_heads=32 n_kv_heads=8 window=0 rotary_dim=64")


# -- the names in the lowered program ----------------------------------------------------------


def test_the_convolutions_ops_carry_its_names_under_attn():
    """The lowered program's locations: the conv mixer's two projections under
    ``attn/gated_conv``, its elementwise part (the two gates, the pad and the
    taps) under ``attn/gated_conv/gated_conv_core`` — forward and backward — and
    the attention layer's under ``attn/global`` with its core inside."""
    cfg, params, tokens, _ = make("stack", seq=32, remat=True)
    text = jax.jit(jax.grad(lambda p: loss_fn(p, tokens, cfg))).lower(params).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    under = lambda pattern: [n for n in names if re.search(pattern, n)]
    assert under(r"attn\)*/(checkpoint/)?gated_conv/dot_general")  # a scope's name may be wrapped: jvp(attn)/...
    assert under(r"attn\)*/.*gated_conv/gated_conv_core/mul") and under(r"attn\)*/.*gated_conv/gated_conv_core/pad")
    assert under(r"transpose\(jvp\(attn\)\)/.*gated_conv/gated_conv_core/") and under(r"transpose\(jvp\(attn\)\)/.*gated_conv/dot_general")
    assert not under(r"gated_conv_core/dot_general")  # the two projections stay outside the core
    assert under(r"attn\)*/.*global/global_core/")


# -- TrainStep and the Manager ----------------------------------------------------------------------


def test_the_fused_step_is_the_split_pair_on_this_tree():
    cfg, _, tokens, _ = make("stack", seq=32)
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    ts = TrainStep(cfg, optax.adamw(1e-2), mesh)
    params = ts.init_params(jax.random.PRNGKey(0))
    opt = ts.init_opt(params)
    batch = ts.shard_batch(tokens)
    loss, grads = ts.grads(params, batch)
    split_stats = dict(ts.last_stats)
    # `apply` donates its state: the fused step below takes copies of it
    kept = jax.tree_util.tree_map(jnp.copy, (params, opt))
    split = ts.apply(params, opt, grads)
    fused_loss, *fused = ts.step(*kept, batch)
    assert float(loss) == float(fused_loss)
    assert set(ts.last_stats) == set(split_stats) == {"tokens_per_expert", "balance_loss", "rows_held", "window_live_share"}
    assert ts.last_stats["tokens_per_expert"].shape == (4, 16)  # the attention layer's experts, then the three scanned
    for a, b in zip(jax.tree_util.tree_leaves(split), jax.tree_util.tree_leaves(tuple(fused))):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    moved = float(jnp.max(jnp.abs(fused[0]["periods"]["conv.experts"]["conv_w"] - init_params(jax.random.PRNGKey(0), cfg)["periods"]["conv.experts"]["conv_w"])))
    assert moved > 0  # the taps train
    bias = fused[0]["periods"]["conv.experts"]["router_bias"]
    assert float(jnp.max(jnp.abs(bias))) == 0.0  # and the selection bias, which no gradient reaches, stays (AdamW decays 0 to 0)


def conv_train_loop(rank, store_addr, runner, total_steps=3):
    from torchft_tpu.collectives import CollectivesTcp
    from torchft_tpu.manager import Manager
    from torchft_tpu.parallel.ft import FTTrainer

    cfg = TransformerConfig(dtype=jnp.float32, remat=False, **SIZES["stack"])
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[runner.replica_id : runner.replica_id + 1])
    ts = TrainStep(cfg, optax.sgd(0.05), mesh)
    manager = Manager(
        collectives=CollectivesTcp(timeout=timedelta(seconds=10)),
        load_state_dict=None, state_dict=None, min_replica_size=2, replica_id=str(runner.replica_id),
        store_addr=store_addr, rank=rank, world_size=runner.world_size,
        lighthouse_addr=runner.lighthouse_address, timeout=timedelta(seconds=10),
    )
    try:
        trainer = FTTrainer(manager, ts)
        trainer.init(jax.random.PRNGKey(0))
        data = np.random.default_rng(3000 + runner.replica_id * 13)
        while manager.current_step() < total_steps:
            tokens = jnp.asarray(data.integers(0, cfg.vocab_size, (2, 32)), jnp.int32)
            trainer.step(tokens)
            runner.failure_injector.check(rank, manager.current_step())
        return {"params": jax.tree_util.tree_map(np.asarray, trainer.params), "step": manager.current_step()}
    finally:
        manager.shutdown(wait=False)


def test_two_groups_exchange_the_conv_layers_and_heal_them():
    """Two replica groups average the tree with the conv mixers' leaves over the
    Manager (``FTTrainer.step``) for three steps; one is killed after its second
    and the trainer started in its place heals the whole tree from the
    survivor: equal parameters, bit for bit, and the taps have moved."""
    from tests.test_integration import FailureInjector, Runner
    from torchft_tpu.coordination import LighthouseServer

    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
    injectors = [FailureInjector(), FailureInjector().fail_at(0, 2)]
    try:
        with ThreadPoolExecutor(max_workers=2) as ex:
            futs = [
                ex.submit(Runner(
                    replica_id=i, lighthouse_address=lighthouse.address(), failure_injector=inj,
                    train_loop=conv_train_loop,
                ).run_replica)
                for i, inj in enumerate(injectors)
            ]
            results = [f.result(timeout=240) for f in futs]
    finally:
        lighthouse.shutdown()
    assert injectors[1].count == 1  # the kill happened, and a third trainer took the group's place
    a, b = results[0][0], results[1][0]
    assert a["step"] == b["step"] == 3
    la, ta = jax.tree_util.tree_flatten(a["params"])
    lb, tb = jax.tree_util.tree_flatten(b["params"])
    assert ta == tb and {"conv_in", "conv_w", "conv_out"} <= set(a["params"]["periods"]["conv.experts"])
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)
    start = init_params(jax.random.PRNGKey(0), TransformerConfig(dtype=jnp.float32, **SIZES["stack"]))
    assert float(np.max(np.abs(a["params"]["lead"]["conv.dense"]["conv_w"] - np.asarray(start["lead"]["conv.dense"]["conv_w"])))) > 0
