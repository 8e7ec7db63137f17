"""Flash-attention kernel numerics vs the reference jnp implementation
(interpreter mode on CPU; the same kernels compile for TPU)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.ops.attention import attention
from torchft_tpu.ops.pallas.flash_attention import flash_attention


def qkv(b=2, s=256, h=2, d=64, seed=0, dtype=jnp.float32):
    rng = jax.random.PRNGKey(seed)
    ks = jax.random.split(rng, 3)
    return tuple(jax.random.normal(k, (b, s, h, d), dtype) for k in ks)


@pytest.mark.parametrize("causal", [True, False])
def test_forward_matches(causal):
    q, k, v = qkv()
    expect = attention(q, k, v, causal=causal)
    got = flash_attention(q, k, v, causal=causal, block_q=64, block_k=64)
    np.testing.assert_allclose(np.asarray(got), np.asarray(expect), atol=2e-5)


def test_grads_match():
    q, k, v = qkv(s=128)

    def loss_ref(q, k, v):
        return (attention(q, k, v, causal=True) ** 2).sum()

    def loss_flash(q, k, v):
        return (
            flash_attention(q, k, v, causal=True, block_q=64, block_k=64) ** 2
        ).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_fl = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fl, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=3e-4)


def test_uneven_blocks_rejected():
    q, k, v = qkv(s=100)
    with pytest.raises(ValueError, match="multiple"):
        flash_attention(q, k, v, block_q=64, block_k=64)


def test_sharded_flash_in_model_matches_plain():
    """attention_impl='flash' under a dp×tp mesh (shard_map-wrapped pallas)
    must equal the plain GSPMD path."""
    import numpy as onp

    from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn
    from torchft_tpu.parallel.mesh import MeshConfig, make_mesh

    base = dict(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
        d_ff=64, dtype=jnp.float32,
    )
    mesh = make_mesh(MeshConfig(dp=2, tp=2))
    params = init_params(jax.random.PRNGKey(0), TransformerConfig(**base))
    tokens = jnp.asarray(
        onp.random.default_rng(0).integers(0, 64, (4, 128)), jnp.int32
    )
    losses = {}
    for impl in ("flash", "plain"):
        cfg = TransformerConfig(**base, attention_impl=impl)
        with jax.set_mesh(mesh):
            losses[impl] = float(
                jax.jit(lambda p, t, c=cfg: loss_fn(p, t, c, mesh))(params, tokens)
            )
    assert abs(losses["flash"] - losses["plain"]) < 1e-3


def test_bad_attention_impl_rejected():
    from torchft_tpu.models.transformer import TransformerConfig, _use_flash

    with pytest.raises(ValueError, match="attention_impl"):
        _use_flash(TransformerConfig(attention_impl="xla"), 4096)


def test_use_flash_auto_threshold(monkeypatch):
    """The auto rule (the 46x fix): flash only past the per-chip
    scores-memory ceiling; per-chip = global / (dp·fsdp batch shards,
    tp head shards)."""
    from unittest.mock import patch

    import jax.numpy as jnp

    from torchft_tpu.models import transformer as T

    cfg = T.TransformerConfig(attention_impl="auto", n_heads=8, dtype=jnp.bfloat16)

    class FakeMesh:
        def __init__(self, **shape):
            self.shape = shape

    with patch.object(T.jax, "default_backend", return_value="tpu"):
        # b1 h8 s8192: 4 * 8 * 8192^2 = 2.1 GB < 4 GB -> plain (the fix)
        assert not T._use_flash(cfg, 8192, 1)
        # b1 h8 s32768: 34 GB -> flash (the memory-ceiling role)
        assert T._use_flash(cfg, 32768, 1)
        # global b8 would cross the ceiling, but dp=4 shards it 4-way:
        # per-chip 4.3 GB... / 4 = 1.07... scaled: 4*2*8*8192^2 = 4.3 GB
        # per chip at dp=4 -> just over; at dp=8 -> under
        assert not T._use_flash(cfg, 8192, 8, FakeMesh(dp=8))
        assert T._use_flash(cfg, 8192, 32, FakeMesh(dp=2))
        # tp shards heads
        assert not T._use_flash(cfg, 16384, 1, FakeMesh(tp=8))
        # threshold env override
        monkeypatch.setenv("TORCHFT_TPU_FLASH_SCORES_GB", "0.5")
        assert T._use_flash(cfg, 8192, 1)
        monkeypatch.setenv("TORCHFT_TPU_FLASH_SCORES_GB", "not-a-number")
        assert not T._use_flash(cfg, 8192, 1)  # malformed -> default 4 GB
    # non-tpu backend never chooses the pallas kernel
    with patch.object(T.jax, "default_backend", return_value="cpu"):
        assert not T._use_flash(cfg, 32768, 1)


@pytest.mark.parametrize("impl", ["flash", "auto"])
def test_flash_inside_pipeline_region_raises(monkeypatch, impl):
    """No quiet fallback: when flash is wanted (explicitly, or by `auto`
    because plain attention's scores would not fit) inside the pipeline's
    manual region, plain attention cannot fit either — raise."""
    from torchft_tpu.models import transformer as T
    from torchft_tpu.parallel.mesh import MeshConfig, make_mesh

    monkeypatch.setattr(T, "_use_flash", lambda *a, **k: True)
    cfg = T.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=4, head_dim=8,
        d_ff=64, dtype=jnp.float32, pp=2, microbatches=2, attention_impl=impl,
    )
    mesh = make_mesh(MeshConfig(pp=2))
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((4, 96), jnp.int32)  # 96 % 128 != 0: not chunked
    with jax.set_mesh(mesh), pytest.raises(ValueError, match="manual region"):
        jax.jit(lambda p, t: T.loss_fn(p, t, cfg, mesh))(params, tokens)


def test_chunked_loss_matches_dense(monkeypatch):
    """Long-context loss head: chunked cross entropy (scan over the
    unembed, [S,V] logits never materialized) must match the dense path
    to f32 accumulation noise in value and grads."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from torchft_tpu.models import transformer as T

    cfg = T.TransformerConfig(
        vocab_size=64, d_model=32, n_layers=2, n_heads=2,
        head_dim=16, d_ff=64, dtype=jnp.float32,
    )
    params = T.init_params(jax.random.PRNGKey(0), cfg)
    tokens = jnp.asarray(
        np.random.default_rng(0).integers(0, 64, (2, 16)), jnp.int32
    )

    dense = T.loss_fn(params, tokens, cfg, None)
    g_dense = jax.grad(lambda p: T.loss_fn(p, tokens, cfg, None))(params)

    monkeypatch.setenv("TORCHFT_TPU_LOSS_CHUNK_ELEMS", "64")  # force chunking
    chunked = T.loss_fn(params, tokens, cfg, None)
    g_chunk = jax.grad(lambda p: T.loss_fn(p, tokens, cfg, None))(params)

    np.testing.assert_allclose(float(dense), float(chunked), rtol=1e-6)
    for a, b in zip(
        jax.tree_util.tree_leaves(g_dense), jax.tree_util.tree_leaves(g_chunk)
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


class TestChunkedAttention:
    """Round-4 tiered chunked-scan attention: the pure-XLA long-context
    path (s=8192: 15% -> ~31% MFU on v5e). Must be numerically the same
    attention as the plain reference, including across tier boundaries
    and under grad."""

    def _qkv(self, s, b=2, h=4, d=32, seed=0):
        import jax

        key = jax.random.PRNGKey(seed)
        ks = jax.random.split(key, 3)
        shp = (b, s, h, d)
        return tuple(jax.random.normal(k, shp, jnp.float32) for k in ks)

    @pytest.mark.parametrize("s,chunk,tiers", [(512, 128, 4), (256, 64, 1), (384, 64, 3)])
    def test_matches_plain(self, s, chunk, tiers):
        from torchft_tpu.ops.attention import attention, chunked_attention

        q, k, v = self._qkv(s)
        ref = attention(q, k, v, causal=True)
        got = chunked_attention(q, k, v, causal=True, chunk=chunk, tiers=tiers)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)

    def test_non_causal_matches(self):
        from torchft_tpu.ops.attention import attention, chunked_attention

        q, k, v = self._qkv(256)
        ref = attention(q, k, v, causal=False)
        got = chunked_attention(q, k, v, causal=False, chunk=64)
        np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)

    def test_grad_matches_plain(self):
        import jax

        from torchft_tpu.ops.attention import attention, chunked_attention

        q, k, v = self._qkv(256)

        def loss(fn):
            return lambda q, k, v: (fn(q, k, v, causal=True) ** 2).sum()

        gref = jax.grad(loss(attention), argnums=(0, 1, 2))(q, k, v)
        gchk = jax.grad(
            lambda q, k, v: (
                chunked_attention(q, k, v, causal=True, chunk=64, tiers=4) ** 2
            ).sum(),
            argnums=(0, 1, 2),
        )(q, k, v)
        for a, b in zip(gref, gchk):
            np.testing.assert_allclose(a, b, rtol=1e-4, atol=1e-4)

    def test_model_routes_chunked(self, monkeypatch):
        """attention_impl='chunked' trains; auto engages past the S
        threshold (lowered via env for a CPU-sized check)."""
        import jax
        import optax

        from torchft_tpu.models.transformer import (
            TransformerConfig,
            _use_chunked,
        )
        from torchft_tpu.parallel.train_step import TrainStep
        from torchft_tpu.parallel.mesh import MeshConfig, make_mesh

        cfg = TransformerConfig(
            vocab_size=128,
            d_model=64,
            n_layers=2,
            n_heads=4,
            head_dim=16,
            d_ff=128,
            dtype=jnp.float32,
            attention_impl="chunked",
        )
        assert _use_chunked(cfg, 512)
        monkeypatch.setenv("TORCHFT_TPU_ATTN_CHUNKED_MIN_S", "512")
        auto = TransformerConfig(**{**cfg.__dict__, "attention_impl": "auto"})
        assert _use_chunked(auto, 512)
        assert not _use_chunked(auto, 256)

        mesh = make_mesh(MeshConfig())
        ts = TrainStep(cfg, optax.adam(1e-2), mesh)
        params = ts.init_params(jax.random.PRNGKey(0))
        opt = ts.init_opt(params)
        tokens = ts.shard_batch(
            jnp.asarray(
                np.random.default_rng(0).integers(0, 128, (2, 512)), jnp.int32
            )
        )
        loss, _, _ = ts.step(params, opt, tokens)
        assert np.isfinite(float(loss))
