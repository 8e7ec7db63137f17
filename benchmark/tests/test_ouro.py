"""What the ``ouro-*`` configurations bring: the count (``opcounts/ouro.py``)
against numbers worked by hand at the cell's size — a layer, the published
model, the cut, every scope's operations with the stack and the head counted
``ut_steps`` times, which bound is the nearer —, the count against what
``init_params`` places in the rehearsal, the plain reference
(``reference/ouro_decoder.py``) against equations worked by hand, and the two
readers of ``loop_scopes.py`` on op paths and rows written by hand, and where
there is nothing to read. ``tests/test_looped_train.py`` (tier-1) holds the
program's loss and every gradient to the reference."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import loop_scopes
import measure
import opcount
import subscopes
from common import load_json, load_module
from reference import ouro_decoder as ref
from torchft_tpu.models.transformer import TransformerConfig, init_params

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
CONFIG = load_json(os.path.join(BENCH, "configs", "ouro-2_6b-1g.json"))
PUBLISHED = load_json(os.path.join(BENCH, "published", "ouro-2.6b.json"))["config"]
TRAFFIC = load_json(os.path.join(BENCH, "traffic", "fused-b4-s4096.json"))
TC = CONFIG["program"]["transformer_config"]
PEAKS = load_json(os.path.join(BENCH, "peaks.json"))["TPU v5 lite"]
CELL = "ouro-2_6b-1g.fused-s4096"
READERS = ("loop_post_norm_device_s", "loop_exit_device_s")
COUNT = opcount.for_config(CONFIG)
BATCH, SEQ = TRAFFIC["batch"], TRAFFIC["seq"]


# -- the count, by hand ---------------------------------------------------------------


def test_parameters_of_a_layer_of_the_published_model_and_of_the_cut():
    assert (BATCH, SEQ, TC["ut_steps"], TC["n_layers"]) == (4, 4096, 4, 5)
    per = COUNT.layer_params(TC)
    assert per == {"attn": 4 * 2048 * 2048, "ffn": 3 * 2048 * 5632, "norms": 4 * 2048}
    assert sum(per.values()) == 51_388_416  # with the four norms
    tables, rest = 2 * 49152 * 2048, 2048 + 2049  # final norm; the gate's [d, 1] and bias
    assert COUNT.n_params({**TC, "n_layers": 48}) == 48 * 51_388_416 + tables + rest == 2_667_974_657  # "2.6 B"
    assert COUNT.n_params(TC) == 5 * 51_388_416 + tables + rest == 458_272_769
    # a layer is held ONCE whatever the loop: four turns add no parameter
    assert COUNT.n_params({**TC, "ut_steps": 1}) == COUNT.n_params(TC)
    # without the model's own parts it is opcount.py's dense count
    plain = {**TC, "sandwich_norm": False, "exit_gate": False, "ut_steps": 1}
    assert COUNT.n_params(plain) == opcount.n_params(plain)
    assert COUNT.flops_per_token_by_scope(plain, SEQ) == opcount.flops_per_token_by_scope(plain, SEQ)


def test_operations_of_each_scope_at_s4096_with_four_turns():
    by = COUNT.flops_per_token_by_scope(TC, SEQ)
    assert set(by) == {"attn", "ffn", "head_loss"} and COUNT.ffn_scopes(TC) == ("ffn",)
    products = 6 * 4 * 2048 * 2048  # q, k, v, o: forward and twice that backward
    core = 3 * 2 * (2 * 4096 * 2048) / 2  # q k^T and p v at half the square, forward + backward
    assert (products, core) == (100_663_296, 50_331_648.0)
    assert by["attn"] == 20 * (products + core) == 3_019_898_880.0  # 5 layers x 4 turns
    assert by["ffn"] == 20 * 6 * 3 * 2048 * 5632 == 4_152_360_960.0
    assert by["head_loss"] == 4 * (6 * 2048 * 49152 + 6 * 2048) == 2_415_968_256.0  # four passes + the gate's [d, 1]
    total = sum(by.values())
    assert total == COUNT.flops_per_token(TC, SEQ) == 9_588_228_096.0
    assert round(100 * by["head_loss"] / total, 1) == 25.2  # of the counted operations; at 48 layers:
    deep = COUNT.flops_per_token_by_scope({**TC, "n_layers": 48}, SEQ)
    assert round(100 * deep["head_loss"] / sum(deep.values()), 1) == 3.4
    # a step of 16 384 tokens at the bf16 peak
    assert round(total * BATCH * SEQ / PEAKS["bf16_flops_per_s"], 3) == 0.797
    # one turn is a quarter of the stack's and of the head's
    once = COUNT.flops_per_token_by_scope({**TC, "ut_steps": 1}, SEQ)
    assert all(by[scope] == 4 * once[scope] for scope in by)


def test_which_bound_is_the_nearer():
    flops = COUNT.flops_per_token_by_scope(TC, SEQ)
    moved = COUNT.bytes_per_step_by_scope(TC, BATCH, SEQ)
    assert set(moved) == {"attn", "ffn", "head_loss", "optimizer"}
    act = 4 * 4096 * 2048 * 2
    assert moved["attn"] == 5 * 9 * 2 * (4 * 2048 * 2048 + 2 * 2048) + 20 * 5 * act  # (2T + 1) passes of the weights
    assert moved["head_loss"] == 3 * 2 * 2048 * 49152 + 4 * 3 * act
    assert moved["optimizer"] == 26 * 458_272_769
    for scope in ("attn", "ffn", "head_loss"):  # the matmul scopes: operations, by 7 to 60 times
        by_ops = flops[scope] * BATCH * SEQ / PEAKS["bf16_flops_per_s"]
        by_bytes = moved[scope] / PEAKS["hbm_bytes_per_s"]
        assert by_ops > 7 * by_bytes, scope
    # the output norms: bytes, by three orders — 40 bandwidth-bound passes a step forward
    ops = COUNT.post_norm_flops_per_token(TC)["post_norm"] * BATCH * SEQ / PEAKS["bf16_flops_per_s"]
    bts = COUNT.post_norm_bytes_per_step(TC, BATCH, SEQ)["post_norm"] / PEAKS["hbm_bytes_per_s"]
    assert COUNT.post_norm_bytes_per_step(TC, BATCH, SEQ)["post_norm"] == 2 * 20 * 5 * act
    assert bts > 100 * ops and round(bts * 1e3, 2) == 16.39  # ms a step at the memory's speed
    assert COUNT.post_norm_flops_per_token({**TC, "sandwich_norm": False}) == {}


def test_the_count_is_what_init_params_places_in_the_rehearsal():
    rehearsal = load_json(os.path.join(HERE, "rehearsal.json"))["transformer_config"]
    tc = {**TC, **rehearsal}
    assert tc["ut_steps"] == 4 and tc["sandwich_norm"] and tc["exit_gate"]  # tiny sizes, the loop as it is
    cfg = TransformerConfig(**{**tc, "dtype": jnp.dtype(tc["dtype"])})
    placed = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), cfg))))
    assert abs(COUNT.n_params(tc) - placed) <= 1e-3 * placed and COUNT.n_params(tc) == placed


def test_the_configuration_is_the_published_file_cut_in_depth_alone():
    changed = {k for k in PUBLISHED if CONFIG[k] != PUBLISHED[k]}
    assert changed == {"num_hidden_layers", "layer_types"} and CONFIG["layer_types"] == ["full_attention"] * 5
    assert CONFIG["total_ut_steps"] == TC["ut_steps"] == 4 and "share" not in CONFIG
    assert "fused` 16.26 GB" in CONFIG["reduced"]["num_hidden_layers"] and "13.35 GB" in CONFIG["reduced"]["num_hidden_layers"]


# -- the reference, by hand -------------------------------------------------------------


def test_the_references_exit_distribution_and_rotation_by_hand():
    lam = jnp.asarray([[0.5, 0.2], [0.5, 0.9], [0.1, 0.3], [0.7, 0.6]])  # [T, S]: only the first three count
    logit = jnp.log(lam) - jnp.log1p(-lam)
    # states whose first lane is the gate's logit, w = e_0, b = 0
    states = jnp.zeros((4, 2, 3)).at[:, :, 0].set(logit)
    params = {"exit_gate": {"w": jnp.asarray([[1.0], [0.0], [0.0]]), "b": jnp.zeros((1,))}}
    p = ref.exit_distribution(params, states)
    want = np.array([[0.5, 0.2], [0.25, 0.8 * 0.9], [0.025, 0.8 * 0.1 * 0.3], [0.225, 0.8 * 0.1 * 0.7]])
    np.testing.assert_allclose(p, want, rtol=1e-5)
    np.testing.assert_allclose(jnp.sum(p, axis=0), 1.0, rtol=1e-6)
    # the rotation: lane i with lane i + Dh/2, position 1, Dh 4, theta 100: angles 1 and 0.1
    x = jnp.asarray([[[1.0, 2.0, 3.0, 4.0]], [[1.0, 2.0, 3.0, 4.0]]])  # [S = 2, H = 1, 4]
    turned = ref._rope_half(x, 100.0)
    np.testing.assert_allclose(turned[0, 0], [1.0, 2.0, 3.0, 4.0], rtol=1e-6)
    c1, s1, c2, s2 = np.cos(1.0), np.sin(1.0), np.cos(0.1), np.sin(0.1)
    np.testing.assert_allclose(turned[1, 0], [c1 - 3 * s1, 2 * c2 - 4 * s2, 3 * c1 + s1, 4 * c2 + 2 * s2], rtol=1e-5)


def test_the_references_blocks_of_queries_are_the_full_matrixs_rows(monkeypatch):
    q, k, v = (jax.random.normal(key, (12, 2, 4)) for key in jax.random.split(jax.random.PRNGKey(0), 3))
    whole = ref._attention(q, k, v)
    monkeypatch.setattr(ref, "QUERY_BLOCK", 5)
    np.testing.assert_allclose(ref._attention(q, k, v), whole, rtol=1e-6, atol=1e-7)
    scores = jnp.einsum("qhd,khd->hqk", q, k) / 2.0
    scores = jnp.where(jnp.tril(jnp.ones((12, 12), bool))[None], scores, -jnp.inf)
    np.testing.assert_allclose(whole, jnp.einsum("hqk,khd->qhd", jax.nn.softmax(scores, -1), v).reshape(12, 8), rtol=1e-5, atol=1e-6)


def test_the_reference_stands_alone():
    text = open(os.path.join(BENCH, "reference", "ouro_decoder.py")).read()
    assert "torchft_tpu" not in text.split('"""', 2)[2] and 'default_matmul_precision("highest")' in text


# -- the readers -----------------------------------------------------------------------------


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py"), "m_" + name)


def a_run(config=CONFIG, peaks=PEAKS):
    traffic = {"batch": 4, "seq": 4096, "steps_per_unit": 5}
    return measure.Run({}, config, traffic, peaks, [{"group": 0, "trace": {"xplane": "no such file"}}])


def test_the_innermost_name_on_a_path_through_the_two_scans():
    fwd = "jit(tft_fused)/jvp()/while/body/closed_call/while/body/closed_call/checkpoint/attn/checkpoint/post_norm/mul"
    assert subscopes.innermost(fwd, "attn", ("post_norm",)) == "post_norm"
    assert subscopes.innermost(fwd, "ffn", ("post_norm",)) is None  # the other parent's
    again = "jit(tft_fused)/transpose(jvp())/while/body/closed_call/while/body/closed_call/checkpoint/rematted_computation/ffn/post_norm/rsqrt"
    assert subscopes.innermost(again, "ffn", ("post_norm",)) == "post_norm"
    product = "jit(tft_fused)/jvp()/while/body/closed_call/while/body/closed_call/checkpoint/ffn/dot_general"
    assert subscopes.innermost(product, "ffn", ("post_norm",)) == "ffn"
    gate = "jit(tft_fused)/transpose(jvp(head_loss))/exit/jit(log_sigmoid)/jit(softplus)/mul"
    assert subscopes.innermost(gate, "head_loss", ("exit",)) == "exit"
    unembed = "jit(tft_fused)/jvp(head_loss)/while/body/dot_general"
    assert subscopes.innermost(unembed, "head_loss", ("exit",)) == "head_loss"


def test_the_readers_on_rows_written_by_hand(monkeypatch):
    rows = {
        "attn": {"post_norm": 0.021, "attn": 0.400},
        "ffn": {"post_norm": 0.019, "ffn": 0.500},
        "head_loss": {"exit": 0.004, "head_loss": 0.300},
    }
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: rows[parent])
    got = {name: reader(name).compute(a_run()) for name in READERS}
    assert got["loop_post_norm_device_s"] == pytest.approx(0.040)  # both parents' added up
    assert got["loop_exit_device_s"] == 0.004
    assert set(loop_scopes.SPLITS) == set(READERS)


def test_nothing_to_read_is_none_and_never_an_error(monkeypatch):
    # a trace that cannot be opened: every reader gives None
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # a program without the scopes (the parent commit, another architecture)
    monkeypatch.setattr(subscopes, "seconds", lambda run, parent, names, rows_of=None: {parent: 0.5})
    assert all(reader(name).compute(a_run()) is None for name in READERS)
    # one of the two parents' norms alone is not the metric
    monkeypatch.setattr(
        subscopes, "seconds", lambda run, parent, names, rows_of=None: {"post_norm": 0.1} if parent == "attn" else {parent: 0.5}
    )
    assert reader("loop_post_norm_device_s").compute(a_run()) is None


def test_the_new_entries_are_found_by_name_and_list_the_new_cell():
    """By name, not by position: a later PR appends after these."""
    bench = load_json(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    listed = {m["name"]: m for m in bench["per_layer"]}
    for name in READERS:
        assert listed[name]["workloads"] == [CELL] and listed[name]["layer"] == "device compute"
        assert (reader(name).NAME, reader(name).UNIT, reader(name).MOVES) == (name, "s", "step_p50_s")
    names = [m["name"] for m in bench["per_layer"]]
    assert all(names.index(name) > names.index("step_program_first_call_s") for name in READERS)  # after what PR 56 appended
    cell = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert (cell["config"], cell["traffic"], cell["chips"]) == ("ouro-2_6b-1g", "fused-b4-s4096", 1)
    entry = next(c for c in bench["configs"] if c["name"] == "ouro-2_6b-1g")
    assert entry["reduced"] == ["num_hidden_layers", "layer_types"] and entry["source"] == CONFIG["source"]
    assert CELL not in next(m for m in bench["end_to_end"] if m["name"] == "tokens_per_s")["workloads"]
