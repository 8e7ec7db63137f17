"""The plain OLMoE reference (``reference/olmoe_decoder.py``) against the
program's ``loss_fn`` and its gradients, tiny size, float32, CPU. Tolerance as
in ``test_reference.py``: both sides compute in float32 (matmuls at
"highest"); what differs is the order of the sums (rows sorted by expert and a
grouped matmul, a scan, chunked attention on one side; every expert over every
token and none of the rest on the other): 2e-5 relative to the largest entry of
a gradient leaf. A dropped expert or a renormalised router is off by orders
more. ``tests/test_moe.py`` (tier-1) holds the program to this file at more
sizes and loads."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from reference import olmoe_decoder as ref
from torchft_tpu.models.transformer import TransformerConfig, init_params, loss_fn

SIZES = dict(
    vocab_size=97, d_model=32, n_layers=2, n_heads=4, head_dim=8, d_ff=16, n_experts=16, top_k=4,
    qk_norm=True, router_aux_loss_coef=0.01, rope_theta=10000.0, norm_eps=1e-5,
)
RTOL = 2e-5


@pytest.fixture(scope="module")
def setup():
    cfg = TransformerConfig(dtype=jnp.float32, remat=True, **SIZES)
    params = init_params(jax.random.PRNGKey(3), cfg)
    layers = params["layers"]
    # norm weights off 1, or a norm applied without its weight would pass
    layers["ln1"], layers["ln2"] = layers["ln1"] * 1.3, layers["ln2"] * 0.8
    layers["q_norm"] = layers["q_norm"] * jnp.linspace(0.6, 1.5, 32)
    layers["k_norm"] = layers["k_norm"] * jnp.linspace(1.4, 0.7, 32)
    layers["router"] = layers["router"] * 3.0  # loads that differ
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
    assert max(jax.tree_util.tree_leaves(errs)) < RTOL, errs


def test_per_sequence_loss_is_what_the_worker_compares(setup):
    """One sequence a call, the balance term over that sequence's tokens."""
    cfg, params, tokens = setup
    per_seq = ref.per_sequence_loss(params, tokens, SIZES)
    for i in range(2):
        assert float(loss_fn(params, tokens[i : i + 1], cfg)) == pytest.approx(float(per_seq[i]), rel=RTOL)


def test_the_check_would_see_a_dropped_expert_or_a_renormalised_router(setup):
    cfg, params, tokens = setup
    want = float(ref.loss(params, tokens, SIZES))
    for off in (dict(top_k=3), dict(norm_topk_prob=True), dict(router_aux_loss_coef=0.0)):
        assert abs(float(ref.loss(params, tokens, {**SIZES, **off})) - want) > 10 * RTOL * want, off


def test_the_reference_imports_nothing_of_the_program():
    with open(ref.__file__) as f:
        imports = [line for line in f if line.startswith(("import ", "from "))]
    assert imports and not any("torchft_tpu" in line for line in imports)
