"""The Gated DeltaNet stack through ``TrainStep`` and the Manager, the names its
ops carry in the lowered program, what the new cell's program runs and says on
the chip's branch, and all seven older cells' programs held to the parent's (the
kinds of layer and the reference are ``tests/test_gdn.py``'s): a file of its own,
so that these compile-heavy tests are handed to a worker of their own in a run
with several."""

import contextlib
import functools
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
from tests.test_gdn import ROOT, SIZES, make, paths_to_kernels
from torchft_tpu.models import transformer as T
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
from torchft_tpu.parallel.train_step import TrainStep


CELLS_PROGRAMS = {
    # (batch, positions), sha256 of the jaxpr of loss_fn's value and gradient at the cell's sizes on the chip's branch,
    # and the first 16 of the sha256 of its ``_say_once`` lines: what 4817540 — the parent of the PR that brought
    # ``gdn_layers``, ``norm_zero_centered``, ``attn_output_gate``, ``shared_expert_gate``, the softmax gate's
    # renormalisation and the layers' aux by NAME — traced, letter for letter, computed there and here by one script
    "olmo1b-1g": ((8, 2048), "73b3ad2e4c16cf95dbaa9a851fae74342c302e8e3ab0adcee3edcbad96db533a", "1bcfe2dfb3ff35a0"),
    "olmo1b-4g": ((8, 2048), "73b3ad2e4c16cf95dbaa9a851fae74342c302e8e3ab0adcee3edcbad96db533a", "1bcfe2dfb3ff35a0"),
    "olmoe-1g": ((8, 2048), "65b119828cd26a22a39bc945227fb3cef92f2b8ae09109a8c17c196e5a896d2d", "1bcfe2dfb3ff35a0"),
    # kimi-linear-1g: re-pinned by the PR that took ``_mix_kda``'s q | k | v out of the block's checkpoint (2ca37b1f…927d
    # at 080a434 and before). Old text against new, primitive counts of the whole program with the kernels' bodies left
    # out: ``dot_general`` 733 -> 721, a KDA layer's 3 projections gone from the forward scan's body, the same 3 from
    # the backward scan's recomputation and their 3 + 3 gradients (to the input, to the weight) from the backward
    # body — 12 a layer — for 3 + 3 + 3 at [8, 2, 1024, ·] outside the scans; the mixer scan's ``xs`` is (blocks, (q, k, v))
    # where it was blocks, its carry's taps three arrays where they were one; the convolution and SiLU stand once for
    # each of q, k, v where they stood once over the concatenation (``logistic`` 58 -> 74, ``pad`` 375 -> 435, the
    # taps' ``mul`` / ``slice`` / ``add`` with them; the products' and the filters' ``concatenate`` gone). The kernels'
    # equations did not move: ``tests/test_mla_rope_mtp_train.CELLS_KERNELS`` holds its digests, and the lines said too.
    # The four cells whose experts a sigmoid gate chooses, re-pinned by the PR that took ``jnp.take_along_axis`` out of
    # ``_route`` (kimi-linear-1g 3d95496b…f10a, laguna-xs2-1g bd50d408…eeb0, joyai-flash-1g 959938be…5bd7, lfm2-8b-a1b-1g
    # 48a3dee1…11b8 at b50bcfa and before; the first of them itself re-pinned as the comment above says). Old text
    # against new, primitive counts of the whole program with the kernels' bodies left out, kimi-linear-1g (four sparse
    # layers written out; laguna-xs2-1g moves by the same numbers, joyai-flash-1g — one scanned layer and the module's —
    # and lfm2-8b-a1b-1g by half of each): ``gather`` 492 -> 484, one a sparse layer a forward pass (the layer's and
    # the one ``remat`` runs again), and ``scatter-add`` 390 -> 386, its transpose, one a layer; with them the gather's
    # index arithmetic (``lt`` 2278 -> 2270, ``add`` 3074 -> 3062, ``reshape`` 965 -> 957). In their place ``_chosen``:
    # ``iota`` 885 -> 893 and ``eq`` 1705 -> 1713 (the expert axis against the chosen indices, a forward pass),
    # ``select_n`` 4422 -> 4426 and ``reduce_sum`` 767 -> 783 (the select and the sum over E, forward; the same select
    # and the sum over k, backward), ``broadcast_in_dim`` 6129 -> 6181, ``convert_element_type`` 2920 -> 2928. No other
    # primitive's count moved; the lines said did not, nor the six other configurations' programs.
    # kimi-linear-1g (and qwen3-next-80b-a3b-1g in ``tests/test_looped_train.CELLS_PROGRAMS``) re-pinned by the PR that
    # hands the delta rule's block inverse from the forward kernels to the backward kernels as a second residual
    # (kimi-linear-1g c77a0955…2fa8, qwen3-next-80b-a3b-1g f987432a…867f at bfd8bba and before). Old text against new:
    # each of the 8 ``kda_fwd`` / 6 ``gdn_fwd`` calls has one more result (3 -> 4: o, starts, the inverse
    # [2, 32, 8, 128, 128] bfloat16, the final state), each of the 4 ``kda_bwd`` / 3 ``gdn_bwd`` calls one more operand
    # (8 -> 9), and the residual rides beside ``starts`` through the ``custom_vjp`` and, in kimi-linear-1g, through
    # ``_by_decay``'s two ``cond``s, whose exact branch makes zeros of it: ``broadcast_in_dim`` 6181 -> 6185, one a KDA
    # layer, and no other primitive's count of the whole program (kernels' bodies left out) moved in either. Inside the
    # bodies, ``dot_general`` at the four heads of a grid step: ``kda_bwd`` 376 -> 256 and ``gdn_bwd`` 262 -> 142 (the
    # inverse's ten products a head in three bfloat16 passes gone: 30 x 4), ``kda_fwd`` 216 and ``gdn_fwd`` 176 as they
    # were. The lines said did not move, nor kimi-linear-1g's ``flash_fwd`` / ``flash_bwd`` equations
    # (``tests/test_mla_rope_mtp_train.CELLS_KERNELS``: its digest of the other kernels did, b428188e…c248 before), nor
    # the eight other configurations' programs.
    # All four (and qwen3-next-80b-a3b-1g in ``tests/test_looped_train.CELLS_PROGRAMS``) re-pinned by the PR that moves the
    # held expert layer's rows by the count of live ones (``ops/layers._live_rows``; kimi-linear-1g 95884449…bc64,
    # laguna-xs2-1g bca2e146…d5c5, joyai-flash-1g 6f42f5a0…efaa, lfm2-8b-a1b-1g 89c3681a…2287, qwen3-next-80b-a3b-1g
    # d04fb3af…0055 at 92920b9 and before). Old text against new, primitive counts of the whole program with the kernels'
    # bodies left out, kimi-linear-1g (four sparse layers written out; laguna-xs2-1g and qwen3-next-80b-a3b-1g move by
    # the same numbers, joyai-flash-1g and lfm2-8b-a1b-1g by half of each): ``while`` 0 -> 20 and ``empty`` 0 -> 20 —
    # a layer's five moves of rows (dispatch, the combine's rows into token order, ``dy`` by row, the rows' gradients into
    # token order, dispatch recomputed) in the first window's branch of the layer's ``cond``; ``every_window``'s branch
    # and the gates' 128-wide cells keep their one gather — ``dynamic_update_slice`` 16 -> 36 and ``dynamic_slice``
    # 480 -> 500 (a pass's indices out, its rows in), ``min`` 32 -> 52 (the last pass's start), ``gather`` 484 as it was:
    # the same gathers, 20 of them now of 512 places in a loop's body where they were of the window's m; the loops' index
    # arithmetic, ``jax.lax``'s own and no jitted helper's (``add`` 3062 -> 3142, ``lt`` 2270 -> 2330, ``select_n`` 4426 ->
    # 4466, ``mul`` 2001 -> 2021, ``jit`` 5878 as it was) and ``window_live_share`` a layer (of the ``div`` 920 -> 944 and
    # ``convert_element_type`` 2928 -> 2972 four each, ``broadcast_in_dim`` 6185 -> 6189, ``concatenate`` 1194 -> 1195,
    # ``reshape`` 957 -> 958). No other primitive's count moved; the lines said did not, nor any ``pallas_call`` equation
    # (``tests/test_mla_rope_mtp_train.CELLS_KERNELS``), nor the programs of olmo1b-1g, olmo1b-4g, olmoe-1g
    # (``moe_dropless`` is a path of its own) and ouro-2_6b-1g.
    "kimi-linear-1g": ((2, 8192), "cc8a5bf3a088965f2ebdc7646e00a24e8607833b758d1463aab7965f38dd9084", "efeaeed97ccba4c3"),
    "laguna-xs2-1g": ((2, 8192), "fff2093b75eb0dc94979323804b0efc5dbcafb846fdd8288c0cdf1dc0664623b", "2e3b7f09d4732e39"),
    "joyai-flash-1g": ((2, 8192), "7a555ecf1ac27d1a22f69d07f2b4c7ba78ee1d2fba664a3111a569a1c01e6515", "a6aa64069f77d113"),
    "lfm2-8b-a1b-1g": ((2, 8192), "60537e3b0b4b4a9f639fee9a44b050d7d3126bcb67a01999fd5bea4e897efb9c", "647d94df3e9b6744"),
}
NEW_CELL = "qwen3-next-80b-a3b-1g"


@functools.lru_cache(maxsize=None)
def cells_program(name, shape, devices=1):
    """(jaxpr of ``loss_fn``'s value and gradient, its ``_say_once`` lines) of a
    benchmark configuration at a cell's size, on the chip's branch; traced
    under a mesh of ``devices`` devices where that is more than one."""
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        tc = json.load(f)["program"]["transformer_config"]
    cfg = TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    said = []
    say = lambda kind, key, **fields: said.append(kind + " " + " ".join(f"{k}={v}" for k, v in fields.items()))
    # a pinned text is a fresh process's: what this process traced before (all of tests/test_gdn_train.py ahead of this
    # file in one worker, for one) changes which sub-jaxprs are one object, and the printed text hoists those
    jax.clear_caches()
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    mesh = contextlib.nullcontext() if devices == 1 else jax.set_mesh(make_mesh(MeshConfig(fsdp=devices), devices=jax.devices()[:devices]))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), mock.patch.object(T, "_say_once", say), mesh:
        jaxpr = jax.make_jaxpr(jax.value_and_grad(lambda p, t: loss_fn(p, t, cfg)))(params, jax.ShapeDtypeStruct(shape, jnp.int32))
    return jaxpr, said


@pytest.mark.parametrize("name", list(CELLS_PROGRAMS))
def test_the_seven_older_cells_programs_and_what_they_say_are_the_parents(name):
    shape, program, lines = CELLS_PROGRAMS[name]
    jaxpr, said = cells_program(name, shape)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    assert hashlib.sha256(text.encode()).hexdigest() == program
    assert hashlib.sha256("\n".join(said).encode()).hexdigest()[:16] == lines


SIGMOID_CELLS = ("kimi-linear-1g", "laguna-xs2-1g", "joyai-flash-1g", "lfm2-8b-a1b-1g")


@pytest.mark.parametrize("name", SIGMOID_CELLS)
def test_a_sigmoid_gates_program_reads_its_weights_without_a_gather_of_the_scores(name):
    """On the chip's branch, at the cell's size: no ``gather`` reads and no
    ``scatter-add`` writes the router's [T, E] float32 scores anywhere in the
    program — under ``moe/router`` or not, in the forward, in what ``remat`` runs
    again or in the backward (the parent's held 8 + 4 in kimi-linear-1g: a scalar
    gather of 131 072 elements took 1.34 ms on the chip). The walk is on the
    right arrays: it finds the gate's ``top_k`` of them, under the router's name."""
    from tests.test_moe import gathers_of_scores

    jaxpr, _ = cells_program(name, CELLS_PROGRAMS[name][0])
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        tc = json.load(f)["program"]["transformer_config"]
    chosen = gathers_of_scores(jaxpr.jaxpr, 2 * 8192, tc["n_experts"], primitives=("top_k",))
    assert tc["router_gate"] == "sigmoid" and chosen and all("/router" in stack for _, stack in chosen)
    assert gathers_of_scores(jaxpr.jaxpr, 2 * 8192, tc["n_experts"]) == []


def test_the_new_cells_softmax_layer_runs_the_kernel_once_and_says_its_heads():
    """`qwen3-next-80b-a3b-1g.fused-s8192` on the chip's branch: the one softmax
    layer is the Pallas kernel at 256 lanes, 16 query heads over 2 — one
    ``flash_fwd`` and one ``flash_bwd`` in the whole step, its output and row
    statistics kept under ``remat`` — and no layer calls the per-channel
    delta rule's kernels (``kda_fwd`` / ``kda_bwd``: the Gated DeltaNet layers
    have their own pair, the test below). The one ``attention_path`` line says
    which core, tile and heads; the ``layer_pattern`` line the period scanned once."""
    from torchft_tpu.ops.pallas.flash_attention import CORE_LSE, CORE_OUT

    jaxpr, said = cells_program(NEW_CELL, (2, 8192))
    calls = kernel_calls(jaxpr.jaxpr)
    assert (calls["flash_fwd"], calls["flash_bwd"], calls[CORE_OUT], calls[CORE_LSE]) == (1, 1, 1, 1)
    assert not [name for name in calls if "kda" in str(name)]
    (line,) = [text for text in said if text.startswith("attention_path ")]
    assert line.startswith("attention_path impl=flash block_q=512 block_k=512 batch=2 seq=8192 head_dim=256 value_dim=256 ")
    assert "reason=auto on a tpu: the fastest core measured at this (seq, head_dim)" in line
    assert line.endswith("n_heads=16 n_kv_heads=2 window=0 rotary_dim=64")
    (pattern,) = [text for text in said if text.startswith("layer_pattern ")]
    assert "lead=- period=gdn.experts,gdn.experts,gdn.experts,full.experts repeats=1 experts_held=32 experts=512 batch=2 seq=8192" in pattern


def test_the_new_cells_gdn_layers_run_the_kernel_pair_and_say_so():
    """The three Gated DeltaNet layers on the chip's branch: every block of
    1 024 positions of every layer is the Pallas kernel pair with one decay a
    head — a layer and block ``gdn_fwd`` twice (the layer's forward, and the
    forward the block's checkpoint runs again: the mixer checkpoints itself
    and is not under the layer's ``remat`` besides) and ``gdn_bwd`` once,
    3 layers x 8 blocks, each in its own ``jit`` under the scans alone: no
    ``cond`` around a call (no call's decay picks a path). Every layer's
    ``gdn_core_path`` line names the kernel. Traced under a mesh of two devices
    the same configuration calls no such kernel and says ``jax.numpy``."""
    jaxpr, said = cells_program(NEW_CELL, (2, 8192))
    calls = kernel_calls(jaxpr.jaxpr)
    assert (calls["gdn_fwd"], calls["gdn_bwd"]) == (2 * 3 * 8, 3 * 8)
    paths = paths_to_kernels(jaxpr.jaxpr)
    assert paths["gdn_fwd"] == {("scan", "scan", "jit"), ("scan", "scan", "remat2", "jit")}
    assert paths["gdn_bwd"] == {("scan", "scan", "remat2", "jit")}
    lines = [text for text in said if text.startswith("gdn_core_path ")]
    assert lines == ["gdn_core_path core=gdn_kernel heads=32 key_heads=16 head_dim=128 chunk=64 batch=2 block=1024"] * 3
    sharded, said = cells_program(NEW_CELL, (2, 1024), devices=2)
    calls = kernel_calls(sharded.jaxpr)
    assert not [name for name in calls if "gdn" in str(name)] and calls["flash_fwd"] == 1
    lines = [text for text in said if text.startswith("gdn_core_path ")]
    assert lines == ["gdn_core_path core=jax.numpy heads=32 key_heads=16 head_dim=128 chunk=64 batch=2 block=1024"] * 3


# -- the names in the lowered program ----------------------------------------------------------


def test_the_new_parts_ops_carry_their_names_under_attn_and_moe():
    """The ``op_name`` of the compiled program's ops, what a device trace
    carries: a Gated DeltaNet mixer's projections under ``attn/gdn``, its
    convolution, its decay and gates and its chunked rule under ``conv``,
    ``gates`` and ``gdn_core`` inside — forward and backward —, the softmax
    layer under ``attn/global`` with ``global_core`` and the gate on its output
    ``out_gate`` inside; and in the lowered text the shared expert's gate
    inside ``moe/shared``."""
    cfg, params, tokens, _ = make("stack", seq=32, remat=True)
    lowered = jax.jit(jax.grad(lambda p: loss_fn(p, tokens, cfg))).lower(params)
    names = set(re.findall(r'op_name="([^"]+)"', lowered.compile().as_text()))
    under = lambda pattern: [n for n in names if re.search(pattern, n)]
    block = r"attn/gdn/while/body/closed_call/"  # the mixer's scan over blocks of positions, each under its checkpoint
    assert under(r"jvp\(\)/.*" + block + "dot_general") and under(r"transpose\(jvp\(\)\)/.*" + block + ".*dot_general")
    assert under(block + "conv/") and under(block + "gates/dot_general") and under(block + "gates/exp")
    assert under(block + r"gdn_core/.*dot_general") and under(r"transpose\(jvp\(\)\)/.*attn/gdn/.*gdn_core/")
    assert not under(r"gdn_core/.*(logistic|softplus|log1p)")  # the decay and the gates stay outside the core
    assert under(r"attn/.*global/global_core/") and under(r"attn/global/out_gate/exp") and under(r"transpose\(jvp\(\)\)/.*global/out_gate/")
    assert not under(r"gdn/.*global") and not under(r"global/.*gdn")
    located = set(re.findall(r'loc\("([^"]+)"', lowered.as_text(debug_info=True)))
    assert "moe/shared/logistic" in located and [n for n in located if re.search(r"moe/.*shared/dot_general", n)]
    assert [n for n in located if re.search(r"moe/.*router/", n)]


# -- TrainStep and the Manager ----------------------------------------------------------------------


STATS = {"tokens_per_expert", "balance_loss", "rows_held", "window_live_share", "shared_gate_mean", "gdn_decay_min", "gdn_beta_mean"}


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
    assert set(ts.last_stats) == set(split_stats) == STATS
    assert ts.last_stats["tokens_per_expert"].shape == (4, 16) and ts.last_stats["shared_gate_mean"].shape == (4,)
    assert ts.last_stats["gdn_decay_min"].shape == ts.last_stats["gdn_beta_mean"].shape == (3,)  # the three gdn layers
    assert float(jnp.max(ts.last_stats["gdn_decay_min"])) < 0.0
    for a, b in zip(jax.tree_util.tree_leaves(split), jax.tree_util.tree_leaves(tuple(fused))):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    start = init_params(jax.random.PRNGKey(0), cfg)["periods"]
    for kind, leaf in (("gdn.experts", "a_log"), ("gdn.experts", "w_ba"), ("full.experts", "q_norm"), ("full.experts", "shared_scale")):
        assert float(jnp.max(jnp.abs(fused[0]["periods"][kind][leaf] - start[kind][leaf]))) > 0, leaf  # they train


COUNTERS = []


def gdn_train_loop(rank, store_addr, runner, total_steps=3):
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


def test_two_groups_exchange_the_gdn_layers_heal_them_and_say_their_counters(monkeypatch):
    """Two replica groups average the tree with the Gated DeltaNet and gated
    softmax leaves over the Manager (``FTTrainer.step``) for three steps; one
    is killed after its second and the trainer started in its place heals the
    whole tree from the survivor: equal parameters, bit for bit. Each step's
    ``loss_sync`` carries ``tft.gdn.counters`` and ``tft.moe.counters``."""
    from tests.test_integration import FailureInjector, Runner
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.parallel import ft

    annotate = ft.tracing.annotate
    monkeypatch.setattr(
        ft.tracing, "annotate",
        lambda name, **stats: (COUNTERS.append((name, stats)) if name.endswith(".counters") else None) or annotate(name, **stats),
    )
    del COUNTERS[:]
    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
    injectors = [FailureInjector(), FailureInjector().fail_at(0, 2)]
    try:
        with ThreadPoolExecutor(max_workers=2) as ex:
            futs = [
                ex.submit(Runner(
                    replica_id=i, lighthouse_address=lighthouse.address(), failure_injector=inj,
                    train_loop=gdn_train_loop,
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
    assert ta == tb and {"w_ba", "w_z", "a_log", "dt_bias", "shared_scale"} <= set(a["params"]["periods"]["gdn.experts"])
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)
    gdn = [stats for name, stats in COUNTERS if name == "gdn.counters"]
    moe = [stats for name, stats in COUNTERS if name == "moe.counters"]
    assert len(gdn) >= 6 and len(moe) == len(gdn)  # three steps of two groups, and the healed one's
    for stats in gdn:
        assert set(stats) == {"step", "decay_min", "beta_mean", "shared_gate_mean"}
        assert stats["decay_min"] < 0.0 and 0.3 < stats["beta_mean"] < 0.7 and 0.3 < stats["shared_gate_mean"] < 0.7
    for stats in moe:  # 2 x 32 tokens x 4 chosen x 4 layers routed; a quarter of the experts held
        assert stats["rows_routed"] == 2 * 32 * 4 * 4 and 0 < stats["rows_held"] < stats["rows_routed"]
        # rows held over the window's slots, mean and fullest of the four layers: the row moves cost by it
        bound = T._held_row_bound(TransformerConfig(**SIZES["stack"]), 2 * 32 * 4)
        assert stats["window_live_share"] == pytest.approx(stats["rows_held"] / (4 * bound))
        assert stats["window_live_share"] <= stats["window_live_share_max"] < 2
