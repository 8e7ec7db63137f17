"""ResNet-18 model family unit tests (the CIFAR-10 DDP config;
reference train_ddp.py:34-80 trains the torchvision equivalent)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from torchft_tpu.models import resnet

# compile-heavy slow tier: excluded from the default run (pyproject addopts)
pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def model():
    cfg = resnet.ResNetConfig(dtype=jnp.float32)
    params, stats = resnet.init(jax.random.PRNGKey(0), cfg)
    return cfg, params, stats


def test_param_count_matches_resnet18(model):
    _, params, _ = model
    n = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(params))
    # torchvision resnet18 CIFAR variant: ~11.17M
    assert 11_100_000 < n < 11_250_000, n


def test_train_step_updates_running_stats_and_learns(model):
    cfg, params, stats = model
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((8, 32, 32, 3)), jnp.float32)
    y = jnp.asarray(rng.integers(0, 10, 8), jnp.int32)

    vg = jax.jit(
        jax.value_and_grad(
            lambda p, s: resnet.loss_fn(p, s, x, y, cfg), has_aux=True
        )
    )
    (loss0, new_stats), grads = vg(params, stats)
    assert np.isfinite(float(loss0))
    # running stats moved off their init
    assert float(jnp.abs(new_stats["stem"]["bn"]["mean"]).sum()) > 0
    # one SGD step reduces the loss on the same batch
    lr = 0.1
    params2 = jax.tree_util.tree_map(lambda p, g: p - lr * g, params, grads)
    (loss1, _), _ = vg(params2, new_stats)
    assert float(loss1) < float(loss0)


def test_eval_uses_running_stats(model):
    cfg, params, stats = model
    x = jnp.asarray(
        np.random.default_rng(1).standard_normal((4, 32, 32, 3)), jnp.float32
    )
    logits, st = resnet.apply(params, stats, x, cfg, train=False)
    assert logits.shape == (4, 10)
    # eval must not mutate state
    for a, b in zip(
        jax.tree_util.tree_leaves(stats), jax.tree_util.tree_leaves(st)
    ):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_apply_is_deterministic(model):
    cfg, params, stats = model
    x = jnp.asarray(
        np.random.default_rng(2).standard_normal((2, 32, 32, 3)), jnp.float32
    )
    l1, _ = resnet.apply(params, stats, x, cfg, train=True)
    l2, _ = resnet.apply(params, stats, x, cfg, train=True)
    np.testing.assert_array_equal(np.asarray(l1), np.asarray(l2))
