"""opcount.py against numbers worked by hand: OLMo-1B's widths at 6 layers (dense),
OLMoE-1B-7B's at one layer (64 experts, 8 per token); and the resolver that
gives a configuration its count (``opcount.for_config``)."""

import json
import os

import pytest

import opcount

HERE = os.path.dirname(os.path.abspath(__file__))


def _tc(name="olmo1b-1g"):
    with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
        return json.load(f)["program"]["transformer_config"]


def test_hand_worked_counts():
    tc = _tc()
    attn = 4 * 2048 * 2048          # q, k, v, o
    ffn = 3 * 2048 * 8192           # gate, in, out
    head = 2048 * 50304
    assert opcount.matmul_params(tc) == 6 * (attn + ffn) + head == 505_675_776
    # embed and out tables, two norm weights a layer, the final norm
    assert opcount.n_params(tc) == 6 * (attn + ffn + 2 * 2048) + 2 * head + 2048 == 608_724_992
    # causal attention, forward: q.kT and p.v, 2*S*qkv each over the full
    # square, half of it under the mask; three times that with the backward
    attn_flops = 6 * 3 * (2 * 2 * 2048 * 2048 / 2)
    assert opcount.flops_per_token(tc, 2048) == 6 * 505_675_776 + attn_flops
    assert opcount.flops_per_token(tc, 2048) / 1e9 == pytest.approx(3.185, abs=1e-3)
    assert opcount.flops_per_step(tc, 8, 2048) / 1e12 == pytest.approx(52.18, abs=1e-2)


def test_embed_gather_is_not_counted():
    tc = dict(_tc())
    base = opcount.flops_per_token(tc, 2048)
    tc["vocab_size"] *= 2  # doubles embed AND out; only out is a matmul
    assert opcount.flops_per_token(tc, 2048) - base == 6 * 2048 * 50304


def test_both_configurations_hold_the_same_model():
    assert _tc("olmo1b-1g") == _tc("olmo1b-4g")


# OLMoE-1B-7B's widths (allenai/OLMoE-1B-7B-0125-Instruct config.json: hidden 2048,
# 16 x 128 heads, 64 experts of width 1024, 8 per token, vocab 50304) at one layer,
# under the program's names; written here, not read from a configuration file
OLMOE_1L = dict(
    vocab_size=50304, d_model=2048, n_layers=1, n_heads=16, head_dim=128,
    d_ff=1024, n_experts=64, top_k=8,
)


def test_hand_worked_counts_with_sparse_experts():
    tc = OLMOE_1L
    attn = 4 * 2048 * 2048                  # q, k, v, o
    expert = 3 * 2048 * 1024                # gate, in, out of one expert
    router = 2048 * 64
    head = 2048 * 50304
    assert (attn, 8 * expert, router, head) == (16_777_216, 50_331_648, 131_072, 103_022_592)
    # a token passes through the router and 8 experts
    assert opcount.matmul_params(tc) == attn + 8 * expert + router + head == 170_262_528
    # the program holds all 64, the router, two norm weights, embed and out, the final norm
    layer = attn + 64 * expert + router + 2 * 2048
    assert layer == 419_565_568
    assert opcount.n_params(tc) == layer + 2 * head + 2048 == 625_612_800
    # f32 parameter and moments read and written, one bf16 gradient read (no dtype key: bf16)
    assert opcount.bytes_per_step_optimizer(tc) == 26 * 625_612_800
    assert opcount.bytes_per_step_optimizer({**tc, "dtype": "float32"}) == 28 * 625_612_800
    # top_k absent: 2, what ops/layers.moe_dispatch hard-wires
    two = {k: v for k, v in tc.items() if k != "top_k"}
    assert opcount.matmul_params(two) == attn + 2 * expert + router + head
    assert opcount.n_params(two) == opcount.n_params(tc)


@pytest.mark.parametrize("tc, ffn", [(None, "ffn"), (OLMOE_1L, "moe")])
def test_scopes_add_up_to_the_whole(tc, ffn):
    tc = tc or _tc()
    by_scope = opcount.flops_per_token_by_scope(tc, 2048)
    assert set(by_scope) == {"attn", ffn, "head_loss"} and opcount.ffn_scope(tc) == ffn
    assert sum(by_scope.values()) == pytest.approx(opcount.flops_per_token(tc, 2048), rel=1e-12)
    d, layers = tc["d_model"], tc["n_layers"]
    assert by_scope["head_loss"] == 6 * d * tc["vocab_size"]
    # projections, and causal scores: 2 matmuls x 2*S*qkv at half the square, x3 with the backward
    assert by_scope["attn"] == layers * (6 * 4 * d * d + 3 * 2 * 2 * 2048 * d / 2)


def test_no_experts_is_the_dense_count_to_the_digit():
    tc = _tc()
    for zero in ({}, {"n_experts": 0}, {"n_experts": 0, "top_k": 8}):
        assert opcount.matmul_params({**tc, **zero}) == 505_675_776
        assert opcount.n_params({**tc, **zero}) == 608_724_992
        assert opcount.flops_per_token({**tc, **zero}, 2048) == 6 * 505_675_776 + 6 * 3 * (2 * 2 * 2048 * 2048 / 2)
    assert opcount.flops_per_token_by_scope(tc, 2048)["ffn"] == 6 * 6 * 3 * 2048 * 8192


def test_hand_worked_bytes_by_scope():
    """The other bound of each scope's roofline: what crosses the scope's edge, bf16."""
    tc = _tc()
    act = 8 * 2048 * 2048 * 2                       # one [tokens, d_model] activation
    attn = 3 * 2 * (4 * 2048 * 2048) + 5 * act      # weights read twice and their gradient written; 5 activations
    ffn = 3 * 2 * (3 * 2048 * 8192) + 5 * act
    head = 3 * 2 * (2048 * 50304) + 3 * act
    assert (attn, ffn, head) == (436_207_616, 637_534_208, 819_462_144)
    assert opcount.bytes_per_step_by_scope(tc, 8, 2048) == {
        "attn": 6 * attn, "ffn": 6 * ffn, "head_loss": head, "optimizer": 26 * 608_724_992,
    }
    # at these shapes the operations are the nearer bound of every matmul scope (v5e: 197 TFLOP/s, 819 GB/s)
    flops = opcount.flops_per_token_by_scope(tc, 2048)
    for scope in ("attn", "ffn", "head_loss"):
        assert flops[scope] * 8 * 2048 / 197e12 > 10 * opcount.bytes_per_step_by_scope(tc, 8, 2048)[scope] / 819e9
    # with experts all 64 and the router are read; a single sequence of 16 tokens is bound by that traffic
    moe = opcount.bytes_per_step_by_scope(OLMOE_1L, 8, 2048)
    assert moe["moe"] == 3 * 2 * (64 * 3 * 2048 * 1024 + 2048 * 64) + 5 * act and "ffn" not in moe
    few = opcount.flops_per_token_by_scope(OLMOE_1L, 16)["moe"] * 16 / 197e12
    assert few < opcount.bytes_per_step_by_scope(OLMOE_1L, 1, 16)["moe"] / 819e9


# -- the resolver: a configuration's own count, or this file's

A_COUNT = """
def n_params(tc):
    return 7

def flops_per_token_by_scope(tc, seq):
    return {"attn": 1.0 * seq, "ffn": 2.0, "moe": 3.0, "head_loss": 4.0}

def bytes_per_step_by_scope(tc, batch, seq):
    return {"attn": 1, "ffn": 2, "moe": 3, "head_loss": 4, "optimizer": 5}

def ffn_scopes(tc):
    return ("ffn", "moe")
"""


def test_a_configuration_without_the_key_is_counted_by_opcount_py():
    tc = _tc()
    for config in ({"program": {"transformer_config": tc}}, {"program": {"opcount": None}}, {"program": {}}, {}):
        assert opcount.for_config(config) is opcount
    assert all(callable(getattr(opcount, fn)) for fn in opcount.INTERFACE)
    assert opcount.ffn_scopes(tc) == ("ffn",) and opcount.ffn_scopes(OLMOE_1L) == ("moe",)
    # the three configuration files name none: their numbers are this file's, as before
    for name in ("olmo1b-1g", "olmo1b-4g", "olmoe-1g"):
        with open(os.path.join(HERE, "..", "configs", name + ".json")) as f:
            assert opcount.for_config(json.load(f)) is opcount


def test_a_named_count_is_the_file_of_that_name(tmp_path, monkeypatch):
    monkeypatch.setattr(opcount, "OPCOUNTS_DIR", str(tmp_path))
    (tmp_path / "latent.py").write_text(A_COUNT)
    counts = opcount.for_config({"program": {"opcount": "latent"}})
    assert counts is not opcount and counts.n_params({}) == 7 and counts.ffn_scopes({}) == ("ffn", "moe")
    assert sum(counts.flops_per_token_by_scope({}, 8).values()) == 17.0


def test_a_missing_file_and_a_missing_function_are_errors_that_name_the_file(tmp_path, monkeypatch):
    monkeypatch.setattr(opcount, "OPCOUNTS_DIR", str(tmp_path))
    with pytest.raises(FileNotFoundError, match=r"'latent'.*latent\.py"):
        opcount.for_config({"program": {"opcount": "latent"}})
    (tmp_path / "latent.py").write_text(A_COUNT.replace("def ffn_scopes", "def ffn_scope").replace("def n_params", "n_params = 7\ndef params"))
    with pytest.raises(AttributeError, match=r"latent\.py lacks n_params, ffn_scopes"):
        opcount.for_config({"program": {"opcount": "latent"}})
