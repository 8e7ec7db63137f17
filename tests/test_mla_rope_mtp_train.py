"""The latent layers with positions and the multi-token-prediction module through
``TrainStep`` and the Manager, the sixteen shares of an expert layer, the names
the module's ops carry, and the two patterned cells' programs held to the
parent's (the layer kinds and the reference are ``tests/test_mla_rope_mtp.py``'s):
a file of its own, so that these compile-heavy tests are handed to a worker of
their own in a run with several."""

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

from tests.test_mla_rope_mtp import ROOT, SIZES, STACK, make, ref
from tests.test_window_gqa import kernel_equations
from torchft_tpu.models import transformer as T
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
from torchft_tpu.parallel.train_step import TrainStep


CELLS_PROGRAMS = {
    # sha256 of the jaxpr of loss_fn's value and gradient at the cell's sizes (b2 x s8192, the chip's branch): a latent
    # layer with q_lora_rank 0 and no mla_rope_theta, window and grouped layers, the held experts and the head trace to
    # the program they traced to before this file's fields existed — but for what PR 47 moved on purpose (at 625ab82
    # and 6356440: c74dd6980153edfe…57cf and d55d8dc15e9fd85d…30cc). Old text against new, equation by equation with
    # the variables renumbered (primitive counts of the whole program, kernels' bodies left out): two ``name``
    # equations a call of the flash kernel (``attn_core_out``, ``attn_core_lse``: 1 call in kimi-linear-1g, 5 in
    # laguna-xs2-1g), one ``reduce_precision`` a call (``jax.checkpoint`` puts it on what a policy lets it keep), the
    # policy of every ``_remat`` checkpoint (None -> ``save_only_these_names``; ``_mix_kda``'s bare one stays None),
    # and ONE ``pallas_call`` a softmax layer gone: the ``flash_fwd`` of the recomputation. Nothing else.
    # kimi-linear-1g since the PR that took ``_mix_kda``'s q | k | v out of the block's checkpoint (2ca37b1f…927d before
    # it): the three products a KDA layer out of both scan bodies and once at the full sequence, the mixer scan's second
    # ``xs`` — old text against new in ``tests/test_gdn_train.CELLS_PROGRAMS``' comment; CELLS_KERNELS below did not move.
    # Both since the PR that took ``jnp.take_along_axis`` out of ``_route``'s sigmoid branch (3d95496b…f10a and
    # bd50d408…eeb0 at b50bcfa and before): ``gather`` 492 -> 484 / 418 -> 410 (one a sparse layer a forward pass) and
    # ``scatter-add`` 390 -> 386 / 365 -> 361 (its transpose), ``_chosen``'s ``iota``, ``eq``, ``select_n`` and
    # ``reduce_sum`` over the experts in their place — the whole list in the same comment; CELLS_KERNELS did not move.
    # kimi-linear-1g since the PR that hands the delta rule's block inverse from ``kda_fwd`` to ``kda_bwd`` as a second
    # residual (c77a0955…2fa8 at bfd8bba and before): one more result a ``kda_fwd`` call (8 of them), one more operand a
    # ``kda_bwd`` call (4), the residual beside ``starts`` through ``_by_decay``'s ``cond``s (``broadcast_in_dim``
    # 6181 -> 6185: the exact branch's zeros) — the whole list in the same comment. In CELLS_KERNELS below the flash
    # kernel's two equations did not move and the digest of the others did (b428188ee614c248 before: the twelve KDA
    # equations are new, ``kda_bwd``'s body with 256 ``dot_general`` where it had 376; the grouped matmuls' are the parent's).
    # Every configuration with ``n_experts_held`` re-pinned by the PR that moves the held layer's rows by the
    # count of live ones (``ops/layers._live_rows``): a ``while`` over passes of 512 places into ``jax.lax.empty`` where a
    # gather of the window's m places stood, and ``window_live_share`` a layer — old digests and counts against new in
    # ``tests/test_gdn_train.CELLS_PROGRAMS``' comment.
    "kimi-linear-1g": "cc8a5bf3a088965f2ebdc7646e00a24e8607833b758d1463aab7965f38dd9084",
    "laguna-xs2-1g": "fff2093b75eb0dc94979323804b0efc5dbcafb846fdd8288c0cdf1dc0664623b",
}
CELLS_KERNELS = {
    # the programs' ``pallas_call`` equations, each printed on its own (``tests/test_window_gqa.kernel_equations``):
    # the flash kernel's by digest, the others' (KDA's, the grouped matmuls') as one digest of theirs. Every one is,
    # letter for letter, an equation the parent's program (6356440) held; what the parent held besides is the forward
    # of each checkpoint's recomputation and the forward pass's own call with its row statistics unread (the same
    # equation but for ``_`` where the statistics are bound): kimi-linear-1g ``flash_fwd`` 0a203b9237cb127a +
    # 55a80147b80989f2 -> 0a203b9237cb127a; laguna-xs2-1g 2 x 397a704a4de61c10 + 3 x 63c7d8251e46e74c +
    # 3 x 5c4213ebc846dd3e + 2 x 8e4669ff79cb390d -> the last five; ``flash_bwd`` and the rest equal at both commits.
    "kimi-linear-1g": ({"flash_fwd": ["0a203b9237cb127a"], "flash_bwd": ["79821667815a0b73"]}, "862f0484c3af2135"),
    "laguna-xs2-1g": (
        {"flash_fwd": ["5c4213ebc846dd3e"] * 3 + ["8e4669ff79cb390d"] * 2, "flash_bwd": ["c8ac0340a8878e7c"] * 3 + ["e221363c77edb6f9"] * 2},
        "1c7e2d9585a8dd16",
    ),
}


@functools.lru_cache(maxsize=None)
def _cells_program(name):
    """The chip's branch of the cell's program, traced once for both tests of it."""
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        tc = json.load(f)["program"]["transformer_config"]
    cfg = TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    # a pinned text is a fresh process's: what this process traced before (all of tests/test_gdn_train.py ahead of this
    # file in one worker, for one) changes which sub-jaxprs are one object, and the printed text hoists those
    jax.clear_caches()
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, 8192), jnp.int32)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"):
        return jax.make_jaxpr(jax.value_and_grad(lambda p, t: loss_fn(p, t, cfg)))(params, tokens)


@pytest.mark.parametrize("name", list(CELLS_PROGRAMS))
def test_the_two_patterned_cells_programs_are_unchanged(name):
    text = re.sub(r"0x[0-9a-f]+", "0x", str(_cells_program(name)))
    assert hashlib.sha256(text.encode()).hexdigest() == CELLS_PROGRAMS[name]


@pytest.mark.parametrize("name", list(CELLS_KERNELS))
def test_the_two_patterned_cells_kernel_equations_are_the_parents(name):
    kernels = kernel_equations(_cells_program(name).jaxpr)
    flash = {k: kernels.pop(k) for k in ("flash_fwd", "flash_bwd")}
    others = hashlib.sha256(json.dumps(kernels, sort_keys=True).encode()).hexdigest()[:16]
    assert (flash, others) == CELLS_KERNELS[name]


# -- the share -----------------------------------------------------------------------------------


def test_the_sixteen_shares_of_a_layer_add_up_to_the_uncut_layer():
    """Sixteen chips, one of sixteen experts each: the routed parts of all
    sixteen shares plus the shared expert counted once are the layer of the
    uncut reference, selection bias and all."""
    d, e, f = 32, 16, 16
    keys = jax.random.split(jax.random.PRNGKey(0), 8)
    whole = {
        "router": 3.0 * jax.random.normal(keys[0], (d, e)) * d**-0.5,
        "router_bias": 0.05 * jax.random.normal(keys[1], (e,)),
        "w_gate": jax.random.normal(keys[2], (e, d, f)) * d**-0.5,
        "w_in": jax.random.normal(keys[3], (e, d, f)) * d**-0.5,
        "w_out": jax.random.normal(keys[4], (e, f, d)) * f**-0.5,
        "shared_gate": jax.random.normal(keys[5], (d, f)) * d**-0.5,
        "shared_in": jax.random.normal(keys[6], (d, f)) * d**-0.5,
        "shared_out": jax.random.normal(keys[7], (f, d)) * f**-0.5,
    }
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 48, d))
    sizes = dict(STACK, n_experts_held=1)
    with jax.default_matmul_precision("highest"):
        want = ref._experts(whole, x, dict(sizes, n_experts_held=0, expert_share_index=0))
        shared = ref._swiglu(x, whole["shared_gate"], whole["shared_in"], whole["shared_out"])
        routed, rows = [], []
        for share in range(16):
            cfg = TransformerConfig(dtype=jnp.float32, **dict(sizes, expert_share_index=share))
            lp = {k: (v[share : share + 1] if k in ("w_gate", "w_in", "w_out") else v) for k, v in whole.items()}
            y, (_, counts, held, _) = jax.jit(lambda lp, x, cfg=cfg: T._ffn_moe(lp, x, cfg))(lp, x)
            routed.append(y - shared)
            rows.append(int(held))
            assert int(jnp.sum(counts)) == 2 * 48 * 4  # the router counts over all 16, on every share
    np.testing.assert_allclose(sum(routed) + shared, want, atol=5e-5)
    assert sum(rows) == 2 * 48 * 4 and len(set(rows)) > 1  # every token-expert row on exactly one share


# -- the names in the lowered program ----------------------------------------------------------


def test_every_op_of_the_module_carries_its_name_inside_a_top_level_scope(monkeypatch):
    """The lowered program's locations: the latent layer's parts under
    ``attn/mla``, and the module's ops under ``<scope>/mtp`` for each of the
    four scopes it nests in — with the chunked head, forward and backward."""
    monkeypatch.setattr(T, "_LOSS_CHUNK_ELEMS", 2 * 16 * 64)
    cfg, params, tokens, _ = make("module", seq=32)
    text = jax.jit(jax.grad(lambda p: loss_fn(p, tokens, cfg))).lower(params).as_text(debug_info=True)
    names = set(re.findall(r'loc\("([^"]+)"', text))
    for part in ("q_lora", "kv_lora", "rope", "mla_core"):  # a scope's name may be wrapped: jvp(attn)/mla/...
        assert any(re.search(rf"attn\)*/mla/{part}/", n) for n in names), part
        assert any(re.search(rf"attn\)*/mtp/mla/{part}/", n) for n in names), part
    for scope in ("embed", "attn", "moe", "head_loss"):
        assert any(re.search(rf"(^|[/(]){scope}\)*/mtp/", n) for n in names), scope
    assert any(re.search(r"transpose\(jvp\(head_loss\)\)/mtp/|head_loss/mtp/mul", n) for n in names)  # the custom backward
    # no op names the module outside a top-level scope
    assert not [n for n in names if "/mtp" in n and not re.search(r"(embed|attn|moe|head_loss)\)*/(.*/)?mtp", n)]


# -- TrainStep and the Manager ----------------------------------------------------------------------


def test_the_fused_step_is_the_split_pair_on_this_tree():
    cfg, _, tokens, _ = make("module", seq=32)
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
    assert set(ts.last_stats) == set(split_stats) == {"main_loss", "mtp_loss", "tokens_per_expert", "balance_loss", "rows_held", "window_live_share"}
    assert float(ts.last_stats["mtp_loss"]) == float(split_stats["mtp_loss"])
    for a, b in zip(jax.tree_util.tree_leaves(split), jax.tree_util.tree_leaves(tuple(fused))):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    assert float(jnp.max(jnp.abs(fused[0]["mtp"]["eh_proj"] - np.asarray(split[0]["mtp"]["eh_proj"])))) < 1e-6


COUNTERS = []


def mtp_train_loop(rank, store_addr, runner, total_steps=3):
    from torchft_tpu.collectives import CollectivesTcp
    from torchft_tpu.manager import Manager
    from torchft_tpu.parallel.ft import FTTrainer

    cfg = TransformerConfig(dtype=jnp.float32, remat=False, **SIZES["module"])
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


def test_two_groups_exchange_the_module_heal_it_and_say_both_losses(monkeypatch):
    """Two replica groups average the tree with the ``mtp`` subtree over the
    Manager for three steps; one is killed after its second and the trainer
    started in its place heals the whole tree from the survivor: equal
    parameters, bit for bit. Each step's ``loss_sync`` carries
    ``tft.mtp.counters`` with both losses."""
    from tests.test_integration import FailureInjector, Runner
    from torchft_tpu.coordination import LighthouseServer
    from torchft_tpu.parallel import ft

    annotate = ft.tracing.annotate
    monkeypatch.setattr(
        ft.tracing, "annotate",
        lambda name, **stats: (COUNTERS.append((name, stats)) if name == "mtp.counters" else None) or annotate(name, **stats),
    )
    del COUNTERS[:]
    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
    injectors = [FailureInjector(), FailureInjector().fail_at(0, 2)]
    try:
        with ThreadPoolExecutor(max_workers=2) as ex:
            futs = [
                ex.submit(Runner(
                    replica_id=i, lighthouse_address=lighthouse.address(), failure_injector=inj,
                    train_loop=mtp_train_loop,
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
    assert ta == tb and set(a["params"]["mtp"]) == {"enorm", "hnorm", "eh_proj", "layer", "final_norm"}
    for x, y in zip(la, lb):
        np.testing.assert_array_equal(x, y)
    assert float(np.max(np.abs(a["params"]["mtp"]["eh_proj"] - np.asarray(init_params(jax.random.PRNGKey(0), TransformerConfig(dtype=jnp.float32, **SIZES["module"]))["mtp"]["eh_proj"])))) > 0
    assert len(COUNTERS) >= 6  # three steps of two groups, and the healed one's
    for _, stats in COUNTERS:
        assert set(stats) == {"step", "main_loss", "mtp_loss", "mtp_weight"}
        assert 3.0 < stats["main_loss"] < 6.0 and 3.0 < stats["mtp_loss"] < 6.0 and stats["mtp_weight"] == 0.3
