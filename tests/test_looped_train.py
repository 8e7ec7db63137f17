"""A looped stack on the training path (``ut_steps`` > 1: the same layers run
several times over shared weights, each layer norming its parts' outputs, an
exit gate a loop step, the expected loss over the exits): ``loss_fn`` and every
leaf's gradient against the plain reference, the shared leaves' gradient as
the sum over untied copies, the weighted loss head, the refusals, what a step
says of its exits, and all eight older cells' programs held to the parent's."""

import dataclasses
import functools
import hashlib
import importlib.util
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

from torchft_tpu.models import transformer as T
from torchft_tpu.models.transformer import TransformerConfig, forward, init_params, loss_and_stats, loss_fn
from torchft_tpu.parallel.mesh import MeshConfig, make_mesh
from torchft_tpu.parallel.train_step import TrainStep

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SIZES = dict(
    vocab_size=96, d_model=32, n_layers=2, n_heads=4, head_dim=8, d_ff=48, rope_theta=1e6, rope_pairing="half",
    norm_eps=1e-6, ut_steps=4, sandwich_norm=True, exit_gate=True, exit_entropy_coef=0.1,
)
BATCH, SEQ = 3, 24


def reference():
    spec = importlib.util.spec_from_file_location(
        "ouro_decoder", os.path.join(ROOT, "benchmark", "reference", "ouro_decoder.py")
    )
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def make(seed=3, **changes):
    """(cfg, params, tokens) at a small size in float32, EVERY leaf moved off
    its initial value: a norm's weight at one, or a gate's bias near zero,
    hides a norm or a bias that is not there."""
    cfg = TransformerConfig(**{**SIZES, **changes}, dtype=jnp.float32)
    leaves, tree = jax.tree_util.tree_flatten(init_params(jax.random.PRNGKey(seed), cfg))
    keys = jax.random.split(jax.random.PRNGKey(seed + 100), len(leaves))
    params = jax.tree_util.tree_unflatten(
        tree, [leaf + 0.1 * jax.random.normal(key, leaf.shape) for leaf, key in zip(leaves, keys)]
    )
    tokens = jax.random.randint(jax.random.PRNGKey(seed + 1), (BATCH, SEQ), 0, cfg.vocab_size)
    return cfg, params, tokens


def relative(got, want):
    """max |got - want| over max |want|, leaf by leaf."""
    return jax.tree_util.tree_map(
        lambda a, b: float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30)), got, want
    )


# Both sides are float32 at "highest" precision, so what is left between them is the order of sums: the loss read
# 3e-7 apart and the gradients 4e-7 to 3e-6 of a leaf's largest entry (seeds 3 and 11, dense and chunked head). The
# limits are ~10 x that. What they hold, as read at this size (the test below reads each): the reference with its
# leaves rounded to bfloat16 moves the loss by 1.7e-4 (this CPU's products stay float32 under any precision, so the
# leaves' rounding is all of it: 34 x the limit), no entropy term by 1.0e-1, a loop of 3 by 7.6e-2, no output norms by 2.9e-2.
LOSS_ATOL = 5e-6
GRAD_RTOL = 3e-5


@pytest.mark.parametrize("chunked", [False, True], ids=["dense_head", "chunked_head"])
def test_loss_and_every_leafs_gradient_are_the_references(chunked, monkeypatch):
    """``loss_fn`` and its gradient for EVERY leaf — the gate's two, the four
    norms a layer and the carried final norm among them — against
    ``benchmark/reference/ouro_decoder.py`` under ``jax.grad``, through the
    dense head and through the chunked one (one pass of the T exits as T batches)."""
    cfg, params, tokens = make()
    if chunked:  # the T x B x S x V logits past the budget, three chunks of eight positions
        monkeypatch.setattr(T, "_LOSS_CHUNK_ELEMS", cfg.ut_steps * BATCH * 8 * cfg.vocab_size)
    ref = reference()
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(p, tokens, cfg))(params)
        want, want_grads = jax.value_and_grad(lambda p: ref.loss(p, tokens, SIZES))(params)
    assert abs(float(loss) - float(want)) < LOSS_ATOL
    assert set(grads["layers"]) >= {"ln1", "post_ln1", "ln2", "post_ln2"} and set(grads["exit_gate"]) == {"w", "b"}
    errors = relative(grads, want_grads)
    for path, err in jax.tree_util.tree_leaves_with_path(errors):
        assert err < GRAD_RTOL, (jax.tree_util.keystr(path), err)
    for leaf in jax.tree_util.tree_leaves(want_grads):  # every leaf is in the loss: none of them reads zero
        assert float(jnp.max(jnp.abs(leaf))) > 1e-5


@pytest.mark.parametrize("slip", ["reference_in_bfloat16", "no_entropy_term", "a_loop_of_three", "no_output_norms"])
def test_the_limit_on_the_loss_fails_each_slip(slip):
    """The comparison above is tight enough: the reference computed in
    bfloat16, or with one part of the mathematics left out, is outside it."""
    cfg, params, tokens = make()
    ref = reference()
    sizes = dict(SIZES)
    if slip == "reference_in_bfloat16":
        params_ref = jax.tree_util.tree_map(lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
        context = jax.default_matmul_precision("bfloat16")
    else:
        params_ref, context = params, jax.default_matmul_precision("highest")
        if slip == "no_entropy_term":
            sizes["exit_entropy_coef"] = 0.0
        elif slip == "a_loop_of_three":
            sizes["ut_steps"] = 3
        else:  # this freshly loaded copy of the module alone
            ref._layer = functools.partial(_layer_without_output_norms, ref)
    with jax.default_matmul_precision("highest"):
        loss = float(loss_fn(params, tokens, cfg))
    with context:
        slipped = float(jnp.mean(ref.per_sequence_loss(params_ref, tokens, sizes)))
    assert abs(loss - slipped) > 20 * LOSS_ATOL, (slip, loss, slipped)


def _layer_without_output_norms(ref, w, u, sizes):
    """``ouro_decoder._layer`` with ``N²`` and ``N⁴`` left out."""
    heads, dh = sizes["n_heads"], sizes["head_dim"]
    s = u.shape[0]
    h = ref._rms_norm(u, w["ln1"], sizes["norm_eps"])
    q = ref._rope_half((h @ w["wq"]).reshape(s, heads, dh), sizes["rope_theta"])
    k = ref._rope_half((h @ w["wk"]).reshape(s, heads, dh), sizes["rope_theta"])
    a = u + ref._attention(q, k, (h @ w["wv"]).reshape(s, heads, dh)) @ w["wo"]
    h = ref._rms_norm(a, w["ln2"], sizes["norm_eps"])
    return a + (jax.nn.silu(h @ w["w_gate"]) * (h @ w["w_in"])) @ w["w_out"]


def test_the_shared_leaves_gradient_is_the_sum_over_untied_copies():
    """The same weights laid out as T x L untied layers run ONCE (``ut_steps``
    1, the loop's final norm between the turns put there by hand) give the
    same states, and a shared leaf's gradient is the sum of its T copies'."""
    cfg, params, tokens = make()
    t, layers = cfg.ut_steps, cfg.n_layers
    flat = TransformerConfig(**{**SIZES, "ut_steps": 1, "exit_gate": False, "exit_entropy_coef": 0.0, "n_layers": layers}, dtype=jnp.float32)
    stage_fn = T._make_stage_fn(flat, None)

    def untied_loss(copies, rest):
        """``copies``: the layers' leaves [T, L, ...], one set a turn."""
        x = T._embed_lookup(rest, tokens, flat.dtype)
        states = []
        for turn in range(t):
            x, _ = stage_fn(jax.tree_util.tree_map(lambda a: a[turn], copies), x)
            x = T._norm(flat, x, rest["final_norm"])
            states.append(x)
        return T._exit_loss(rest, jnp.stack(states), tokens, cfg)[0]

    copies = jax.tree_util.tree_map(lambda a: jnp.broadcast_to(a[0], (t,) + a.shape[1:]), params["layers"])
    rest = {k: v for k, v in params.items() if k != "layers"}
    with jax.default_matmul_precision("highest"):
        loss, grads = jax.value_and_grad(lambda p: loss_fn(p, tokens, cfg))(params)
        untied, untied_grads = jax.value_and_grad(untied_loss)(copies, rest)
    assert abs(float(loss) - float(untied)) < LOSS_ATOL
    summed = jax.tree_util.tree_map(lambda a: jnp.sum(a, axis=0)[None], untied_grads)
    for path, err in jax.tree_util.tree_leaves_with_path(relative(grads["layers"], summed)):
        assert err < GRAD_RTOL, (jax.tree_util.keystr(path), err)
    # and the copies differ: no turn's gradient is a quarter of the sum
    wq = untied_grads["wq"]
    assert float(jnp.max(jnp.abs(wq[0] - wq[-1]))) > 1e-3 * float(jnp.max(jnp.abs(wq)))


# -- the weighted head ---------------------------------------------------------------------------------------


def head_inputs(seed=0, n=3, b=2, c=8, d=16, v=40):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    hs = jax.random.normal(keys[0], (n, b, c, d), jnp.float32)
    out = jax.random.normal(keys[1], (d, v), jnp.float32) * d**-0.5
    ts = jax.random.randint(keys[2], (n, b, c), 0, v)
    ms = (jax.random.uniform(keys[3], (n, b, c)) > 0.2).astype(jnp.float32)
    ps = jax.random.uniform(keys[4], (n, b, c), jnp.float32, 0.1, 1.0)
    return hs, out, ts, ms, ps


def plain_nll(hs, out, ts):
    logp = jax.nn.log_softmax(hs @ out, axis=-1)
    return -jnp.take_along_axis(logp, ts[..., None], axis=-1)[..., 0]


def test_the_weighted_head_gives_each_tokens_nll_as_the_weights_cotangent():
    """``_chunked_nll`` under weights: the value is ``sum(nll * m * p) /
    sum(m)``, the second result each position's NLL, the gradients for the
    states and the table those of the plain formula — and the weights'
    cotangent is ``nll * m / sum(m)``, out of the forward scan."""
    hs, out, ts, ms, ps = head_inputs()

    def plain(hs, out, ps):
        return jnp.sum(plain_nll(hs, out, ts) * ms * ps) / jnp.sum(ms)

    with jax.default_matmul_precision("highest"):
        (loss, nll), grads = jax.value_and_grad(lambda *a: T._chunked_nll(a[0], a[1], ts, ms, a[2]), argnums=(0, 1, 2), has_aux=True)(hs, out, ps)
        want, want_grads = jax.value_and_grad(plain, argnums=(0, 1, 2))(hs, out, ps)
        called_plainly = T._chunked_nll(hs, out, ts, ms, ps)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    np.testing.assert_allclose(nll, plain_nll(hs, out, ts), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(called_plainly[0], want, rtol=1e-6)
    for got, ref in zip(grads, want_grads):
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-7)
    np.testing.assert_allclose(grads[2], nll * ms / jnp.sum(ms), rtol=1e-6)  # the cotangent IS the forward's NLL


def _parents_chunked_nll():
    """``_chunked_nll`` as 6d5d1c7 had it, letter for letter (no weights, one result)."""

    def chunk_nll(h_c, out_w, t_c, m_c):
        logits = h_c @ out_w
        target = jnp.take_along_axis(logits, t_c[..., None], axis=-1).astype(jnp.float32)
        logits = logits.astype(jnp.float32)
        mx = jnp.max(logits, axis=-1, keepdims=True)
        lse = jnp.log(jnp.sum(jnp.exp(logits - mx), axis=-1, keepdims=True)) + mx
        return logits, lse, jnp.sum((lse - target)[..., 0] * m_c)

    @jax.custom_vjp
    def chunked_nll(hs, out, ts, ms):
        out_w = out.astype(hs.dtype)

        def body(nll_sum, xt):
            h_c, t_c, m_c = xt
            return nll_sum + chunk_nll(h_c, out_w, t_c, m_c)[2], None

        nll_sum, _ = jax.lax.scan(body, jnp.float32(0.0), (hs, ts, ms))
        return nll_sum / jnp.sum(ms)

    def fwd(hs, out, ts, ms):
        out_w = out.astype(hs.dtype)
        cnt = jnp.sum(ms)

        def body(carry, xt):
            h_c, t_c, m_c = xt
            nll_sum, d_out = carry
            logits, lse, nll_c = chunk_nll(h_c, out_w, t_c, m_c)
            onehot = jax.nn.one_hot(t_c, logits.shape[-1], dtype=logits.dtype)
            dlogits = ((jnp.exp(logits - lse) - onehot) * (m_c / cnt)[..., None]).astype(h_c.dtype)
            dh_c = jnp.einsum("bcv,dv->bcd", dlogits, out_w)
            d_out = d_out + jnp.einsum("bcd,bcv->dv", h_c, dlogits, preferred_element_type=jnp.float32)
            return (nll_sum + nll_c, d_out), dh_c

        (nll_sum, d_out), dhs = jax.lax.scan(body, (jnp.float32(0.0), jnp.zeros(out.shape, jnp.float32)), (hs, ts, ms))
        return nll_sum / cnt, (dhs, d_out.astype(out.dtype))

    def bwd(res, g):
        dhs, d_out = res
        return (g * dhs).astype(dhs.dtype), (g * d_out).astype(d_out.dtype), None, None

    chunked_nll.defvjp(fwd, bwd)
    return chunked_nll


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_without_weights_the_head_is_the_parents_to_the_bit(dtype):
    """Called as the eight older cells call it (mask, no weights), value and
    both gradients are bit-equal to the parent's function; and the weights
    that say the same (all ones) give those bits too."""
    hs, out, ts, ms, _ = head_inputs(seed=5)
    hs = hs.astype(dtype)
    parents = _parents_chunked_nll()
    want, want_grads = jax.value_and_grad(lambda h, o: parents(h, o, ts, ms), argnums=(0, 1))(hs, out)
    loss, grads = jax.value_and_grad(lambda h, o: T._chunked_nll(h, o, ts, ms), argnums=(0, 1))(hs, out)
    ones, ones_grads = jax.value_and_grad(lambda h, o: T._chunked_nll(h, o, ts, ms, jnp.ones_like(ms))[0], argnums=(0, 1))(hs, out)
    for got in ((loss, grads), (ones, ones_grads)):
        np.testing.assert_array_equal(np.asarray(got[0]), np.asarray(want))
        for a, b in zip(got[1], want_grads):
            np.testing.assert_array_equal(np.asarray(a.astype(jnp.float32)), np.asarray(b.astype(jnp.float32)))
    assert str(jax.make_jaxpr(lambda h, o: T._chunked_nll(h, o, ts, ms))(hs, out)).count("scan") == 1


def test_the_exits_probabilities_sum_to_one_a_token_and_say_what_the_reference_says():
    cfg, params, tokens = make(seed=11)
    ref = reference()
    with jax.default_matmul_precision("highest"):
        loss, stats = loss_and_stats(params, tokens, cfg)
        hs, _ = T._hidden_states(params, tokens, cfg)
        states = jnp.stack([ref.hidden_states(params, row, SIZES) for row in tokens], axis=1)  # [T, B, S, D]
        p = jnp.stack([ref.exit_distribution(params, states[:, i]) for i in range(BATCH)], axis=1)
    assert hs.shape == (cfg.ut_steps, BATCH, SEQ, cfg.d_model)
    np.testing.assert_allclose(hs, states, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, atol=1e-6)
    assert set(stats) == {"exit_probs", "exit_entropy", "loss_by_step"}
    assert stats["exit_probs"].shape == stats["loss_by_step"].shape == (cfg.ut_steps,)
    np.testing.assert_allclose(jnp.sum(stats["exit_probs"]), 1.0, atol=1e-6)
    np.testing.assert_allclose(stats["exit_probs"], jnp.mean(p[:, :, :-1], axis=(1, 2)), rtol=1e-4)
    entropy = -jnp.sum(p * jnp.log(p), axis=0)[:, :-1]
    np.testing.assert_allclose(stats["exit_entropy"], jnp.mean(entropy), rtol=1e-4)
    expected = float(jnp.sum(stats["exit_probs"] * stats["loss_by_step"]))  # p and the NLL are nearly independent here
    assert abs(float(loss) + cfg.exit_entropy_coef * float(stats["exit_entropy"]) - expected) < 0.05
    # forward() gives the LAST loop step's logits
    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(forward(params, tokens, cfg), ref.forward(params, tokens, SIZES), rtol=2e-4, atol=2e-4)


def test_without_the_gate_the_loss_reads_the_last_loop_step():
    cfg, params, tokens = make(exit_gate=False, exit_entropy_coef=0.0)
    assert "exit_gate" not in params
    with jax.default_matmul_precision("highest"):
        loss, stats = loss_and_stats(params, tokens, cfg)
        logits = forward(params, tokens, cfg)
    nll = -jnp.take_along_axis(jax.nn.log_softmax(logits[:, :-1], axis=-1), tokens[:, 1:, None], axis=-1)
    assert stats == {} and abs(float(loss) - float(jnp.mean(nll))) < LOSS_ATOL


def test_the_output_norms_stand_in_every_kind_of_layer():
    """``sandwich_norm`` by itself, on a stack with experts: the two leaves a
    layer are there, train, and the layer's other result (the router's
    statistics) still comes through."""
    cfg = TransformerConfig(
        vocab_size=64, d_model=16, n_layers=2, n_heads=2, head_dim=8, d_ff=24, n_experts=4, top_k=2,
        sandwich_norm=True, dtype=jnp.float32,
    )
    params = init_params(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, 64)
    (loss, stats), grads = jax.value_and_grad(lambda p: loss_and_stats(p, tokens, cfg), has_aux=True)(params)
    assert stats["tokens_per_expert"].shape == (2, 4) and np.isfinite(float(loss))
    for name in ("post_ln1", "post_ln2"):
        assert params["layers"][name].shape == (1, 2, 16) and float(jnp.max(jnp.abs(grads["layers"][name]))) > 0
    plain = dataclasses.replace(cfg, sandwich_norm=False)
    assert abs(float(loss_fn({**params, "layers": {k: v for k, v in params["layers"].items() if "post" not in k}}, tokens, plain)) - float(loss)) > 1e-3


# -- what it cannot run with yet ---------------------------------------------------------------------------


@pytest.mark.parametrize("changes, names", [
    (dict(pp=2), "ut_steps=4 with pp=2"),
    (dict(window=8, window_layers=(1,)), "ut_steps=4 with a declared layer pattern"),
    (dict(n_mtp_modules=1), "ut_steps=4 with a multi-token-prediction module"),
])
def test_the_three_refusals_raise_by_name(changes, names):
    cfg = TransformerConfig(**{**SIZES, **changes}, dtype=jnp.float32)
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    tokens = jax.ShapeDtypeStruct((2, 16), jnp.int32)
    for fn in (loss_fn, forward):
        with pytest.raises(ValueError, match=re.escape(names)):
            jax.eval_shape(lambda p, t: fn(p, t, cfg), params, tokens)


@pytest.mark.parametrize("changes, names", [
    (dict(ut_steps=0), "the stack runs at least once"),
    (dict(ut_steps=1), "exit_gate says at which loop step a token leaves"),
    (dict(exit_gate=False), "exit_entropy_coef weighs the entropy of exit_gate's distribution"),
])
def test_fields_that_come_together_are_refused_apart(changes, names):
    with pytest.raises(ValueError, match=re.escape(names)):
        TransformerConfig(**{**SIZES, **changes})


# -- the names in the lowered program, and what the loop says once ---------------------------------------------


def test_the_new_parts_ops_carry_their_names_and_the_loop_says_its_shape():
    cfg, params, tokens = make(remat=True)
    said = []
    say = lambda kind, key, **fields: said.append(kind + " " + " ".join(f"{k}={v}" for k, v in fields.items()))
    with mock.patch.object(T, "_say_once", say):
        lowered = jax.jit(jax.grad(lambda p: loss_fn(p, tokens, cfg))).lower(params)
    names = set(re.findall(r'op_name="([^"]+)"', lowered.compile().as_text()))
    under = lambda pattern: [n for n in names if re.search(pattern, n)]
    loop = r"while/body/.*while/body/"  # the scan over loop steps around the scan over layers
    assert under(r"jvp\(\)/" + loop + r".*attn/.*post_norm/") and under(r"transpose\(jvp\(\)\)/" + loop + r".*attn/.*post_norm/")
    assert under(r"jvp\(\)/" + loop + r".*ffn/.*post_norm/") and under(r"transpose\(jvp\(\)\)/" + loop + r".*ffn/.*post_norm/")
    assert under(r"/jvp\(head_loss\)/exit/") and under(r"/transpose\(jvp\(head_loss\)\)/exit/")
    assert not under(r"post_norm/.*dot_general")  # a norm, not a product
    (line,) = [text for text in said if text.startswith("loop_shape ")]
    assert line == (
        f"loop_shape ut_steps=4 layers=2 applications=8 sandwich_norm=True exit_gate=True exit_entropy_coef=0.1 "
        f"batch={BATCH} seq={SEQ}"
    )


# -- TrainStep and the Manager --------------------------------------------------------------------------------


def test_the_fused_step_is_the_split_pair_and_keeps_the_exits_statistics():
    cfg, _, tokens = make()
    mesh = make_mesh(MeshConfig(), devices=jax.devices()[:1])
    ts = TrainStep(cfg, optax.adamw(1e-2), mesh)
    params = ts.init_params(jax.random.PRNGKey(0))
    opt = ts.init_opt(params)
    batch = ts.shard_batch(tokens)
    loss, grads = ts.grads(params, batch)
    assert set(ts.last_stats) == {"exit_probs", "exit_entropy", "loss_by_step"}
    kept = jax.tree_util.tree_map(jnp.copy, (params, opt))
    split = ts.apply(params, opt, grads)
    fused_loss, *fused = ts.step(*kept, batch)
    assert float(loss) == float(fused_loss) and ts.last_stats["exit_probs"].shape == (4,)
    for a, b in zip(jax.tree_util.tree_leaves(split), jax.tree_util.tree_leaves(tuple(fused))):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-7)
    start = init_params(jax.random.PRNGKey(0), cfg)
    for path in (("exit_gate", "w"), ("exit_gate", "b"), ("layers", "post_ln1"), ("layers", "post_ln2"), ("final_norm",)):
        a, b = fused[0], start
        for key in path:
            a, b = a[key], b[key]
        assert float(jnp.max(jnp.abs(a - b))) > 0, path  # they train


COUNTERS = []
MODEL_COUNTERS = ("loop.counters", "moe.counters", "mtp.counters", "gdn.counters")


def looped_train_loop(rank, store_addr, runner, total_steps=2, sizes=SIZES):
    from torchft_tpu.collectives import CollectivesTcp
    from torchft_tpu.manager import Manager
    from torchft_tpu.parallel.ft import FTTrainer

    cfg = TransformerConfig(dtype=jnp.float32, remat=False, **sizes)
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
            trainer.step(jnp.asarray(data.integers(0, cfg.vocab_size, (2, 16)), jnp.int32))
        return {"params": jax.tree_util.tree_map(np.asarray, trainer.params), "step": manager.current_step()}
    finally:
        manager.shutdown(wait=False)


def run_two_groups(train_loop):
    from tests.test_integration import FailureInjector, Runner
    from torchft_tpu.coordination import LighthouseServer

    lighthouse = LighthouseServer(bind="[::]:0", min_replicas=2)
    try:
        with ThreadPoolExecutor(max_workers=2) as ex:
            futs = [
                ex.submit(Runner(
                    replica_id=i, lighthouse_address=lighthouse.address(), failure_injector=FailureInjector(),
                    train_loop=train_loop,
                ).run_replica)
                for i in range(2)
            ]
            return [f.result(timeout=240)[0] for f in futs]
    finally:
        lighthouse.shutdown()


@pytest.fixture
def counters(monkeypatch):
    from torchft_tpu.parallel import ft

    annotate = ft.tracing.annotate
    monkeypatch.setattr(
        ft.tracing, "annotate",
        # what a model says of itself (the exchange's and the builds' counters are every model's)
        lambda name, **stats: (COUNTERS.append((name, stats)) if name in MODEL_COUNTERS else None) or annotate(name, **stats),
    )
    del COUNTERS[:]
    return COUNTERS


def test_two_groups_exchange_the_looped_tree_and_say_their_exits(counters):
    """Two replica groups average the looped tree (shared layers, four norms a
    layer, the gate) over the Manager for two steps: equal parameters, bit for
    bit, and every step's ``loss_sync`` carries ``tft.loop.counters``."""
    a, b = run_two_groups(looped_train_loop)
    assert a["step"] == b["step"] == 2
    for x, y in zip(jax.tree_util.tree_leaves(a["params"]), jax.tree_util.tree_leaves(b["params"])):
        np.testing.assert_array_equal(x, y)
    said = [stats for name, stats in counters if name == "loop.counters"]
    assert len(said) == 4 and {name for name, _ in counters} == {"loop.counters"}
    for stats in said:
        assert set(stats) == {"step", "ut_steps", "exit_entropy"} | {f"exit_p{t}" for t in (1, 2, 3, 4)} | {f"loss_step{t}" for t in (1, 2, 3, 4)}
        assert stats["ut_steps"] == 4 and abs(sum(stats[f"exit_p{t}"] for t in (1, 2, 3, 4)) - 1.0) < 1e-5
        assert 0.0 < stats["exit_entropy"] < np.log(4) + 1e-6 and all(3.0 < stats[f"loss_step{t}"] < 7.0 for t in (1, 2, 3, 4))


def test_any_other_model_says_nothing_of_a_loop(counters):
    plain = {k: v for k, v in SIZES.items() if k not in ("ut_steps", "sandwich_norm", "exit_gate", "exit_entropy_coef")}
    a, b = run_two_groups(functools.partial(looped_train_loop, sizes=plain))
    assert a["step"] == b["step"] == 2 and not counters


# -- the eight older cells' programs -----------------------------------------------------------------------------


CELLS_PROGRAMS = {
    # (batch, positions) and the sha256 of the jaxpr of loss_fn's value and gradient at the cell's sizes on the chip's
    # branch: what 6d5d1c7 — the parent of the PR that brought ``ut_steps``, ``sandwich_norm``, ``exit_gate`` and the
    # head's weights — traced, letter for letter, computed there and here by one script
    # (the four whose experts a sigmoid gate chooses — kimi-linear-1g, laguna-xs2-1g, joyai-flash-1g, lfm2-8b-a1b-1g —
    # re-pinned by the PR that took ``jnp.take_along_axis`` out of ``_route``: ``gather`` -1 a sparse layer a forward
    # pass, ``scatter-add`` -1 a layer, ``_chosen``'s compare, select and sum in their place; old counts against new in
    # ``tests/test_gdn_train.CELLS_PROGRAMS``' comment; kimi-linear-1g and qwen3-next-80b-a3b-1g re-pinned by the PR that
    # hands the delta rule's block inverse from the forward kernels to the backward kernels — c77a0955…2fa8 and
    # f987432a…867f at bfd8bba and before: one more result a ``kda_fwd`` / ``gdn_fwd`` call, one more operand a
    # ``kda_bwd`` / ``gdn_bwd`` call, the backward bodies' ``dot_general`` 376 -> 256 / 262 -> 142, and in kimi-linear-1g
    # ``broadcast_in_dim`` 6181 -> 6185 for the exact branch's zeros; the whole list in the same comment)
    # Every configuration with ``n_experts_held`` re-pinned by the PR that moves the held layer's rows by the
    # count of live ones (``ops/layers._live_rows``): a ``while`` over passes of 512 places into ``jax.lax.empty`` where a
    # gather of the window's m places stood, and ``window_live_share`` a layer — old digests and counts against new in
    # ``tests/test_gdn_train.CELLS_PROGRAMS``' comment.
    "olmo1b-1g": ((8, 2048), "73b3ad2e4c16cf95dbaa9a851fae74342c302e8e3ab0adcee3edcbad96db533a"),
    "olmo1b-4g": ((8, 2048), "73b3ad2e4c16cf95dbaa9a851fae74342c302e8e3ab0adcee3edcbad96db533a"),
    "olmoe-1g": ((8, 2048), "65b119828cd26a22a39bc945227fb3cef92f2b8ae09109a8c17c196e5a896d2d"),
    "kimi-linear-1g": ((2, 8192), "cc8a5bf3a088965f2ebdc7646e00a24e8607833b758d1463aab7965f38dd9084"),
    "laguna-xs2-1g": ((2, 8192), "fff2093b75eb0dc94979323804b0efc5dbcafb846fdd8288c0cdf1dc0664623b"),
    "joyai-flash-1g": ((2, 8192), "7a555ecf1ac27d1a22f69d07f2b4c7ba78ee1d2fba664a3111a569a1c01e6515"),
    "lfm2-8b-a1b-1g": ((2, 8192), "60537e3b0b4b4a9f639fee9a44b050d7d3126bcb67a01999fd5bea4e897efb9c"),
    "qwen3-next-80b-a3b-1g": ((2, 8192), "4dc1c3836effffda86c5d724912e04f538e6ad4203c81e9f57bc5fbccd35ecaa"),
    # PR 71's own cell, pinned at the commit that brought it (a learned key selector in front of every core): the eight
    # rows above are what they were before ``sparse_topk`` existed
    "keye-vl-2_0-30b-a3b-1g": ((1, 16384), "c87f025a820a257463c30d8433ec11daf6b9bfafbcd299da0930e7e0b936303e"),
}
NEW_CELL = "ouro-2_6b-1g"


@functools.lru_cache(maxsize=None)
def cells_program(name, shape):
    """(jaxpr of ``loss_fn``'s value and gradient, its ``_say_once`` lines) of a
    benchmark configuration at a cell's size, on the chip's branch."""
    with open(os.path.join(ROOT, "benchmark", "configs", name + ".json")) as f:
        tc = json.load(f)["program"]["transformer_config"]
    cfg = TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    said = []
    say = lambda kind, key, **fields: said.append(kind + " " + " ".join(f"{k}={v}" for k, v in fields.items()))
    # a pinned text is a fresh process's: what this process traced before (all of tests/test_gdn_train.py ahead of this
    # file in one worker, for one) changes which sub-jaxprs are one object, and the printed text hoists those
    jax.clear_caches()
    params = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), mock.patch.object(T, "_say_once", say):
        jaxpr = jax.make_jaxpr(jax.value_and_grad(lambda p, t: loss_fn(p, t, cfg)))(params, jax.ShapeDtypeStruct(shape, jnp.int32))
    return jaxpr, said


@pytest.mark.parametrize("name", list(CELLS_PROGRAMS))
def test_the_eight_older_cells_programs_are_the_parents(name):
    shape, program = CELLS_PROGRAMS[name]
    jaxpr, said = cells_program(name, shape)
    text = re.sub(r"0x[0-9a-f]+", "0x", str(jaxpr))
    assert hashlib.sha256(text.encode()).hexdigest() == program
    assert not [line for line in said if line.startswith("loop_shape")]


def test_the_new_cells_program_runs_the_kernel_once_a_layer_application():
    """`ouro-2_6b-1g.fused-s4096` on the chip's branch: the flash kernel at 128
    lanes and s4096 under both scans — 20 runs of its forward (5 layers x 4
    turns; output and row statistics kept, so the backward runs none again) and
    20 of its backward — and the lines the program says once."""
    from tests.test_attn_core_remat import kernel_calls
    from torchft_tpu.ops.pallas.flash_attention import CORE_LSE, CORE_OUT

    jaxpr, said = cells_program(NEW_CELL, (4, 4096))
    calls = kernel_calls(jaxpr.jaxpr)
    assert (calls["flash_fwd"], calls["flash_bwd"], calls[CORE_OUT], calls[CORE_LSE]) == (20, 20, 20, 20)
    (line,) = [text for text in said if text.startswith("attention_path ")]
    assert line.startswith("attention_path impl=flash block_q=512 block_k=512 batch=4 seq=4096 head_dim=128 value_dim=128 ")
    assert line.endswith("n_heads=16 n_kv_heads=16 window=0 rotary_dim=128")
    (loop,) = [text for text in said if text.startswith("loop_shape ")]
    assert loop == (
        "loop_shape ut_steps=4 layers=5 applications=20 sandwich_norm=True exit_gate=True exit_entropy_coef=0.1 batch=4 seq=4096"
    )
