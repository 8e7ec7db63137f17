"""opcount.py against numbers worked by hand for OLMo-1B's widths at 6 layers."""

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
