"""Parallelism tests on the virtual 8-device CPU mesh.

Covers what the reference cannot (SURVEY.md §2.3): tensor/sequence/
pipeline/expert parallel shardings of the flagship transformer, ring
attention numerics vs plain attention, and pipeline vs sequential
equivalence.
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from torchft_tpu.models.transformer import (
    TransformerConfig,
    init_params,
    loss_fn,
    param_specs,
)
from torchft_tpu.ops.attention import attention, ring_attention
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
from torchft_tpu.parallel.train_step import TrainStep

CFG = dict(
    vocab_size=128,
    d_model=32,
    n_layers=4,
    n_heads=4,
    head_dim=8,
    d_ff=64,
    dtype=jnp.float32,  # CPU test: keep numerics comparable
)


def tokens(b=8, s=16, seed=0):
    rng = np.random.default_rng(seed)
    return jnp.asarray(rng.integers(0, CFG["vocab_size"], (b, s)), jnp.int32)


class TestRingAttention:
    def test_matches_plain(self):
        mesh = make_mesh(MeshConfig(sp=4, tp=2))
        rng = jax.random.PRNGKey(0)
        q, k, v = (
            jax.random.normal(r, (2, 16, 4, 8), jnp.float32)
            for r in jax.random.split(rng, 3)
        )
        expect = attention(q, k, v, causal=True)
        with jax.set_mesh(mesh):
            got = jax.jit(lambda q, k, v: ring_attention(q, k, v, mesh))(q, k, v)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=2e-5)

    def test_grads_match(self):
        mesh = make_mesh(MeshConfig(sp=4))
        rng = jax.random.PRNGKey(1)
        q, k, v = (
            jax.random.normal(r, (1, 8, 2, 4), jnp.float32)
            for r in jax.random.split(rng, 3)
        )

        def loss_plain(q):
            return attention(q, k, v).sum()

        def loss_ring(q):
            return ring_attention(q, k, v, mesh).sum()

        g1 = jax.grad(loss_plain)(q)
        with jax.set_mesh(mesh):
            g2 = jax.jit(jax.grad(loss_ring))(q)
        np.testing.assert_allclose(np.asarray(g2), np.asarray(g1), atol=2e-5)


class TestRematPolicy:
    def test_dots_matches_all_and_typo_raises(self):
        """remat_policy='dots' (save matmul outputs) must be numerically
        identical to full-layer recompute, and unknown values must raise
        instead of silently paying full recompute (round-5 review)."""
        mesh = make_mesh(MeshConfig())
        t = tokens()
        losses, grads = [], []
        for policy in ("all", "dots"):
            cfg = TransformerConfig(**{**CFG, "remat_policy": policy})
            params = init_params(jax.random.PRNGKey(0), cfg)
            with jax.set_mesh(mesh):
                l, g = jax.jit(
                    jax.value_and_grad(lambda p, t: loss_fn(p, t, cfg, mesh))
                )(params, t)
            losses.append(float(l))
            grads.append(g)
        assert losses[0] == pytest.approx(losses[1], rel=1e-6)
        jax.tree_util.tree_map(
            lambda a, b: np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), atol=1e-5
            ),
            grads[0],
            grads[1],
        )

        bad = TransformerConfig(**{**CFG, "remat_policy": "dot"})
        params = init_params(jax.random.PRNGKey(0), bad)
        with pytest.raises(ValueError, match="remat_policy"):
            with jax.set_mesh(mesh):
                jax.jit(lambda p, t: loss_fn(p, t, bad, mesh))(params, t)


class TestTransformer:
    def test_dense_loss_and_grads(self):
        cfg = TransformerConfig(**CFG)
        mesh = make_mesh(MeshConfig(dp=2, sp=2, tp=2))
        params = init_params(jax.random.PRNGKey(0), cfg)
        with jax.set_mesh(mesh):
            loss = jax.jit(lambda p, t: loss_fn(p, t, cfg, mesh))(params, tokens())
        assert np.isfinite(float(loss))
        assert float(loss) < 2 * np.log(CFG["vocab_size"])

    def test_pipeline_matches_sequential(self):
        base = TransformerConfig(**CFG)
        piped = TransformerConfig(**{**CFG, "pp": 2, "microbatches": 2})
        mesh1 = make_mesh(MeshConfig())
        mesh2 = make_mesh(MeshConfig(pp=2))

        p1 = init_params(jax.random.PRNGKey(0), base)
        # same weights reshaped into [2, L/2] stages
        p2 = jax.tree_util.tree_map(
            lambda a: a.reshape(2, a.shape[1] // 2, *a.shape[2:])
            if a.ndim >= 2 and a.shape[0] == 1
            else a,
            p1,
        )
        t = tokens()
        with jax.set_mesh(mesh1):
            l1 = jax.jit(lambda p, t: loss_fn(p, t, base, mesh1))(p1, t)
        with jax.set_mesh(mesh2):
            l2 = jax.jit(lambda p, t: loss_fn(p, t, piped, mesh2))(p2, t)
        np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)

    def test_moe_expert_parallel(self):
        cfg = TransformerConfig(**{**CFG, "n_experts": 4})
        mesh = make_mesh(MeshConfig(ep=4, tp=2))
        params = init_params(jax.random.PRNGKey(0), cfg)
        with jax.set_mesh(mesh):
            loss = jax.jit(lambda p, t: loss_fn(p, t, cfg, mesh))(params, tokens())
        assert np.isfinite(float(loss))


class TestTrainStep:
    def test_fused_step_learns(self):
        cfg = TransformerConfig(**CFG)
        mesh = make_mesh(MeshConfig(dp=2, sp=2, tp=2))
        ts = TrainStep(cfg, optax.adam(1e-2), mesh)
        params = ts.init_params(jax.random.PRNGKey(0))
        opt_state = ts.init_opt(params)
        t = ts.shard_batch(tokens())
        losses = []
        for _ in range(5):
            loss, params, opt_state = ts.step(params, opt_state, t)
            losses.append(float(loss))
        assert losses[-1] < losses[0]

    def test_split_grads_apply(self):
        cfg = TransformerConfig(**CFG)
        mesh = make_mesh(MeshConfig(dp=2, tp=2, sp=2))
        ts = TrainStep(cfg, optax.sgd(1e-2), mesh)
        params = ts.init_params(jax.random.PRNGKey(0))
        opt_state = ts.init_opt(params)
        t = ts.shard_batch(tokens())
        loss0, grads = ts.grads(params, t)
        # host round-trip (the FT cross-group path)
        host_grads = jax.tree_util.tree_map(np.asarray, grads)
        params, opt_state = ts.apply(params, opt_state, host_grads)
        loss1, _ = ts.grads(params, t)
        assert float(loss1) < float(loss0)

    def test_full_5d_mesh(self):
        """dp x pp x sp x tp all >1 in one step (the dryrun shape)."""
        cfg = TransformerConfig(**{**CFG, "pp": 2, "microbatches": 2})
        mesh = make_mesh(MeshConfig(pp=2, sp=2, tp=2))
        ts = TrainStep(cfg, optax.adam(1e-2), mesh)
        params = ts.init_params(jax.random.PRNGKey(0))
        opt_state = ts.init_opt(params)
        t = ts.shard_batch(tokens())
        loss, params, opt_state = ts.step(params, opt_state, t)
        assert np.isfinite(float(loss))


class TestNoInvoluntaryRemat:
    """Round-4 regression guard (round-3 review missing #2): the sharded
    step must compile without XLA's "[SPMD] Involuntary full
    rematerialization" fallback — it silently replicates a full tensor
    (the embed table, historically) on every device every step. capfd
    sees the C++ absl warning on fd 2."""

    def _run(self, cfg_over, mesh_over):
        cfg = TransformerConfig(**{**CFG, **cfg_over})
        mesh = make_mesh(MeshConfig(**mesh_over))
        ts = TrainStep(cfg, optax.adam(1e-2), mesh)
        params = ts.init_params(jax.random.PRNGKey(0))
        opt_state = ts.init_opt(params)
        t = ts.shard_batch(tokens())
        loss, _, _ = ts.step(params, opt_state, t)
        assert np.isfinite(float(loss))

    def test_fsdp_pp_sp_step_has_no_remat_fallback(self, capfd):
        self._run(
            {"pp": 2, "microbatches": 2}, dict(fsdp=2, pp=2, sp=2)
        )
        assert "Involuntary full rematerialization" not in capfd.readouterr().err

    def test_ep_tp_fsdp_moe_step_has_no_remat_fallback(self, capfd):
        self._run({"n_experts": 4}, dict(ep=2, tp=2, fsdp=2))
        assert "Involuntary full rematerialization" not in capfd.readouterr().err


class TestGradsChain:
    """``TrainStep.grads`` as a chain of programs where the stack can be cut a
    layer at a time (``transformer.grads_chain``), and as the one program it
    was everywhere else."""

    CUT = {
        "dense": ({}, {}),
        "experts_with_a_balance_term": ({"n_experts": 4, "top_k": 2, "router_aux_loss_coef": 0.01}, {}),
        "no_remat": ({"remat": False}, {}),
        "sharded": ({}, {"fsdp": 2, "sp": 2, "tp": 2}),
    }
    # a stack the chain cannot cut, and the sha256 of the text `grads` lowers
    # to at (8, 16) tokens: computed at 17f6eb6, the parent of the PR that
    # brought the chain, and here, by one script
    UNCUT = {
        "pattern": ({"window": 8, "window_layers": (1, 3)}, {}, "5731e1b68b163eceae191c50ba63cc3ad69cb7c5ed151a04a014158219a41a6f"),
        "looped": ({"ut_steps": 2}, {}, "b711d118ab86358c3147b673006b5f34a9f8291fb2ba02ac6b8e8eedddf4f3e6"),
        "mtp": ({"n_mtp_modules": 1}, {}, "b191f883fa53605ba1b0db198f4ae1d57123992f56a55e5210b0804e13b43651"),
        "pp": ({"pp": 2, "microbatches": 2}, {"pp": 2}, "2958b02deed432b2162883c432f8d1b2f662687923208713ac6cbcddc4ac4893"),
    }

    @staticmethod
    def _step(cfg_over, mesh_over):
        cfg = TransformerConfig(**{**CFG, **cfg_over})
        mesh_cfg = MeshConfig(**mesh_over)
        mesh = make_mesh(mesh_cfg, devices=jax.devices()[: mesh_cfg.total])
        return cfg, mesh, TrainStep(cfg, optax.sgd(1e-2), mesh)

    @pytest.mark.parametrize("name", list(CUT))
    def test_the_pieces_assemble_to_the_gradient(self, name):
        """An OLMo-shaped float32 stack: the head's piece first, a layer's from
        the last to the first, the embedding's last; stacked they are
        ``jax.grad`` of ``loss_fn`` and the one program's gradients, and
        ``apply`` takes them as they came."""
        from torchft_tpu.utils.pieces import GradPieces

        cfg, mesh, ts = self._step(*self.CUT[name])
        params = ts.init_params(jax.random.PRNGKey(0))
        t = ts.shard_batch(tokens())
        loss, grads = ts.grads(params, t)
        assert isinstance(grads, GradPieces) and len(grads) == cfg.n_layers + 2
        assert set(grads[0]) == {"final_norm", "out"} and set(grads[-1]) == {"embed"}
        assert all(leaf.shape[:2] == (1, 1) for piece in grads[1:-1] for leaf in jax.tree_util.tree_leaves(piece))
        tree = ts.grads_tree(grads)
        assert jax.tree_util.tree_structure(tree) == jax.tree_util.tree_structure(params)
        with jax.set_mesh(mesh):
            loss1, one_program, stats1 = ts._value_and_grad(params, t)
            wanted = jax.jit(jax.grad(lambda p: loss_fn(p, t, cfg, mesh)))(params)
        np.testing.assert_allclose(float(loss), float(loss1), rtol=1e-6)
        assert set(ts.last_stats) == set(stats1)
        for reference in (wanted, one_program):
            for (path, got), want in zip(jax.tree_util.tree_leaves_with_path(tree), jax.tree_util.tree_leaves(reference)):
                np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6, err_msg=jax.tree_util.keystr(path))
        # the update from the pieces is the update from the tree
        opt = ts.init_opt(params)
        kept = jax.tree_util.tree_map(jnp.copy, (params, opt))
        from_pieces, _ = ts.apply(params, opt, grads)
        with jax.set_mesh(mesh):
            from_tree, _ = ts._apply(*kept, one_program)
        for got, want in zip(jax.tree_util.tree_leaves(from_pieces), jax.tree_util.tree_leaves(from_tree)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-7)

    def test_the_layers_share_one_program(self):
        cfg, mesh, ts = self._step({}, {})
        params = ts.init_params(jax.random.PRNGKey(0))
        t = ts.shard_batch(tokens())
        head, layer, tail = ts._chain
        ts.grads(params, t)
        ts.grads(params, t)
        # L calls a step of ONE executable: the row is an argument, not a constant
        # whichever link gave it its cotangent
        assert (head._cache_size(), layer._cache_size(), tail._cache_size()) == (1, 1, 1)

    @pytest.mark.parametrize("name", list(UNCUT))
    def test_a_stack_the_chain_cannot_cut_is_one_piece_and_the_parents_program(self, name):
        import hashlib

        cfg_over, mesh_over, digest = self.UNCUT[name]
        cfg, mesh, ts = self._step(cfg_over, mesh_over)
        assert ts._chain is None and ts._apply_pieces is None
        params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
        with jax.set_mesh(mesh):
            text = ts._value_and_grad.lower(params, jax.ShapeDtypeStruct((8, 16), jnp.int32)).as_text()
        assert hashlib.sha256(text.encode()).hexdigest() == digest
        params = ts.init_params(jax.random.PRNGKey(0))
        loss, grads = ts.grads(params, ts.shard_batch(tokens()))
        assert jax.tree_util.tree_structure(grads) == jax.tree_util.tree_structure(params)
        assert ts.grads_tree(grads) is grads
