"""The plain reference against the program's ``loss_fn`` and its gradients,
tiny size, float32, CPU. Tolerance: both sides compute in float32 (matmuls
at "highest"); what differs is the order of the sums (scan, chunked
attention and loss on one side, none on the other), a few float32 ulps of
values of order 1 accumulated over a few hundred terms: 2e-5 relative to the
largest entry of a gradient leaf. A term left out is off by orders more."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from reference import dense_decoder as ref
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn

SIZES = dict(
    vocab_size=97, d_model=32, n_layers=3, n_heads=4, head_dim=8, d_ff=48,
    rope_theta=10000.0, norm_eps=1e-6,
)
RTOL = 2e-5


@pytest.fixture(scope="module")
def setup():
    cfg = TransformerConfig(dtype=jnp.float32, remat=True, **SIZES)
    params = init_params(jax.random.PRNGKey(3), cfg)
    # norm weights off 1, or a norm applied without its weight would pass
    params["layers"]["ln1"] = params["layers"]["ln1"] * 1.3
    params["layers"]["ln2"] = params["layers"]["ln2"] * 0.8
    params["final_norm"] = params["final_norm"] * 0.7
    tokens = jnp.asarray(np.random.default_rng(0).integers(0, 97, (2, 24)), jnp.int32)
    return cfg, params, tokens


def test_loss_and_gradients_agree(setup):
    cfg, params, tokens = setup
    with jax.default_matmul_precision("highest"):
        got, g_got = jax.value_and_grad(loss_fn)(params, tokens, cfg)
    want, g_want = jax.value_and_grad(ref.loss)(params, tokens, SIZES)
    assert float(got) == pytest.approx(float(want), rel=RTOL)
    errs = jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))), g_got, g_want
    )
    worst = max(jax.tree_util.tree_leaves(errs))
    assert worst < RTOL, errs


def test_per_sequence_loss_is_what_the_worker_compares(setup):
    cfg, params, tokens = setup
    per_seq = ref.per_sequence_loss(params, tokens, SIZES)
    for i in range(2):
        assert float(loss_fn(params, tokens[i : i + 1], cfg)) == pytest.approx(float(per_seq[i]), rel=RTOL)


def test_the_check_would_see_a_dropped_term(setup):
    """Without RoPE the loss moves by far more than the tolerance."""
    cfg, params, tokens = setup
    no_rope = dict(SIZES, rope_theta=1e30)  # every angle ~0: positions vanish
    assert abs(float(ref.loss(params, tokens, no_rope)) - float(ref.loss(params, tokens, SIZES))) > 1e-3
