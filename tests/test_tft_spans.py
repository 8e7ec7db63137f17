"""The program's own spans on the profiler's clock (docs/observability.md,
"Spans in the profiler's trace"): three ``FTTrainer.step``s of a small model on
a one-group Manager over CollectivesTcp, read back from the ``.xplane.pb`` a
``jax.profiler`` session wrote, and from the Tracer ring without a session.
"""

import contextlib
import re

import jax
import jax.numpy as jnp
import numpy as np

from conftest import SPANS_BUCKET_BYTES as BUCKET_BYTES, run_steps, spans_cfg
from torchft_tpu.models.transformer import init_params
from torchft_tpu.telemetry import tracing

CFG = spans_cfg()

STEP_SPANS = (
    "step", "quorum.start", "shard_batch", "grads", "exchange", "commit",
    "commit.prepare", "apply", "loss_sync",
)
MAIN_THREAD = (
    "exchange.d2h_issue", "exchange.plan", "exchange.d2h_wait", "exchange.pack",
    "exchange.submit", "exchange.tail_wait", "exchange.reassemble",
    "exchange.counters",
)
# these carry step, bucket and bytes; the ring, a layer below, only its bytes
PER_BUCKET = ("exchange.d2h_wait", "exchange.pack", "exchange.submit", "exchange.h2d")
# the step's sums: attributes of the ``exchange`` span, stats of its counters
EXCHANGE_SUMS = {
    "step", "buckets", "buckets_reused", "buckets_avg_in_ring", "d2h_pages_kept", "bytes_d2h",
    "d2h_wait_s", "pack_s", "tail_wait_s", "utime_s", "stime_s",
    "pack_bytes", "pack_aliased_bytes", "h2d_bytes", "buckets_from_source",
    "ring_wait_s", "ring_pull_s", "ring_reduce_s", "ring_pump_s", "ring_pull_bytes", "ring_reduce_bytes",
    "pieces", "bytes_under_grads",
}
# by bucket, on the span alone (in a trace every bucket has events of its own)
EXCHANGE_BY_BUCKET = {"bucket_landed_s", "bucket_ring_end_s", "bucket_under_grads"}


def n_params() -> int:
    shapes = jax.eval_shape(lambda: init_params(jax.random.PRNGKey(0), CFG))
    return sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(shapes))


def main_line(lines):
    (main,) = [ln for ln in lines if "step" in ln]
    return main


def test_every_span_is_in_the_trace_on_its_thread(traced):
    lines = traced.lines
    main = main_line(lines)
    assert len(main["step"]) == 3
    assert [s["step_num"] for _, _, s in main["step"]] == [0, 1, 2]
    # that each name is there at all is a case of its own in
    # tests/test_telemetry_readers.py; here, where it lies: children inside
    # their step, on the main thread's line
    for name in STEP_SPANS[1:] + MAIN_THREAD:
        for s, e, _ in main[name]:
            assert any(s0 <= s and e <= e0 for s0, e0, _ in main["step"]), name
    for s, e, _ in main["commit.prepare"]:
        assert any(s0 <= s and e <= e0 for s0, e0, _ in main["commit"])
    # the manager's and the RPC layer's existing spans gained their twins;
    # the quorum itself is the quorum thread's, apart from the step's call
    names = {n for ln in lines for n in ln}
    assert {"quorum_rpc", "should_commit", "should_commit_rpc"} <= names
    assert "quorum" not in main and any("quorum" in ln for ln in lines)
    # the ring runs on the collectives op thread; a bucket's h2d follows it
    # there, or runs inline on the main thread when the ring was done before
    # ddp attached the continuation (world size 1: the ring is ~0)
    (op,) = [ln for ln in lines if "exchange.ring" in ln]
    assert op is not main
    h2d = sum(len(ln.get("exchange.h2d", ())) for ln in (op, main))
    assert h2d == len(op["exchange.ring"]) == len(main["exchange.submit"])
    assert sum(len(ln.get("exchange.h2d", ())) for ln in lines) == h2d


def test_bucket_stats_tie_the_threads_together(traced):
    lines = traced.lines
    main = main_line(lines)
    everything = {}
    for ln in lines:
        for name, evs in ln.items():
            everything.setdefault(name, []).extend(evs)
    total = 4 * n_params()
    assert total > 2 * BUCKET_BYTES
    for step in (0, 1, 2):
        keys = {}
        for name in PER_BUCKET:
            mine = [s for _, _, s in everything[name] if s["step"] == step]
            assert sum(s["bytes"] for s in mine) == total, name
            keys[name] = sorted(s["bucket"] for s in mine)
        assert len(set(map(tuple, keys.values()))) == 1, keys
        assert keys["exchange.submit"] == list(range(len(keys["exchange.submit"])))
    # the op thread runs the rings in submission order: order is the link
    ring = sorted(everything["exchange.ring"])
    assert [s["bytes"] for _, _, s in ring] == [s["bytes"] for _, _, s in sorted(main["exchange.submit"])]
    # one group: nothing to divide by, so the ring says it did not divide
    assert all(
        set(s) == {"bytes", "queued_s", "divisor"} and s["queued_s"] >= 0 and s["divisor"] == 0
        for _, _, s in ring
    )
    assert "exchange.average" not in everything
    (_, _, counters), *_ = [c for c in main["exchange.counters"] if c[2]["step"] == 1]
    assert counters["buckets"] == len(keys["exchange.submit"]) >= 3
    assert counters["bytes_d2h"] == total
    assert set(counters) == EXCHANGE_SUMS
    assert all(counters[key] >= 0 for key in counters)
    assert counters["buckets_avg_in_ring"] == 0
    # every byte of the tree is packed once and put back once; one group has
    # no neighbour to wait for or pull from
    assert counters["pack_bytes"] == counters["h2d_bytes"] == total
    assert 0 <= counters["pack_aliased_bytes"] <= total
    assert all(counters[k] == 0 for k in counters if k.startswith("ring_"))
    # the pack says where its largest copy lands within a page
    assert all(0 <= s["dst_ahead_b"] < 4096 for _, _, s in main["exchange.pack"])
    # a zero-length carrier at the end of its exchange
    for (s, e, _), (s0, e0, _) in zip(main["exchange.counters"], main["exchange"]):
        assert s0 <= s and e <= e0 and e - s < 1e6


def test_each_ring_is_followed_by_its_account(traced):
    """``exchange.ring.account``: zero-length, on the op thread, one a ring,
    after it and before the next."""
    lines = traced.lines
    (op,) = [ln for ln in lines if "exchange.ring" in ln]
    rings, accounts = sorted(op["exchange.ring"]), sorted(op["exchange.ring.account"])
    assert sum(len(ln.get("exchange.ring.account", ())) for ln in lines) == len(accounts) == len(rings)
    from torchft_tpu.collectives import RING_ACCOUNT

    for i, ((rs, re_, ring), (s, e, account)) in enumerate(zip(rings, accounts)):
        assert e - s < 1e6 and re_ <= s
        assert i + 1 == len(rings) or e <= rings[i + 1][0]
        assert set(account) == set(RING_ACCOUNT) | {"bytes", "plane"}
        assert account["bytes"] == ring["bytes"]
        # world size 1: no hop ran
        assert all(account[k] == 0 for k in RING_ACCOUNT)


def test_without_a_session_the_ring_gains_step_spans_only(traced, spans_train_step, monkeypatch):
    traced_losses, traced_checksum = traced.losses, traced.checksum
    tracing.TRACER.clear()
    # ... and the annotations change nothing: same loss, same parameters
    monkeypatch.setattr(tracing, "annotate", lambda name, **stats: contextlib.nullcontext())
    losses, checksum = run_steps(spans_train_step, 3, monkeypatch)
    assert losses[0] == traced_losses[0] and checksum == traced_checksum

    spans = tracing.TRACER.recent()
    steps = [s for s in spans if s["name"] == "step"]
    # replica:step:epoch, with the step the root was opened for
    assert all(s["trace_id"].startswith("spans_0") for s in steps)
    assert [s["trace_id"].split(":")[1] for s in steps] == ["0", "1", "2"]
    last = steps[-1]
    children = [s for s in spans if s.get("parent_id") == last["span_id"]]
    # commit.prepare is the commit's child
    assert sorted(s["name"] for s in children) == sorted(set(STEP_SPANS[1:]) - {"commit.prepare"})
    for s in children:
        assert s["trace_id"] == last["trace_id"]
        assert last["t0_mono_ns"] <= s["t0_mono_ns"]
        assert s["t0_mono_ns"] + s["dur_s"] * 1e9 <= last["t0_mono_ns"] + last["dur_s"] * 1e9 + 1e6
    assert last["attrs"]["committed"] is True
    (exchange,) = [s for s in children if s["name"] == "exchange"]
    # the step's sums reach /trace, the JSONL and the piggyback untraced too
    assert set(exchange["attrs"]) == EXCHANGE_SUMS | EXCHANGE_BY_BUCKET
    # the chain's pieces, a bucket each, landed and rung in the order given
    assert exchange["attrs"]["pieces"] == exchange["attrs"]["buckets"] == CFG.n_layers + 2
    for key in ("bucket_landed_s", "bucket_ring_end_s"):
        at = [float(t) for t in exchange["attrs"][key].split(",")]
        assert len(at) == CFG.n_layers + 2 and at == sorted(at) and at[0] > 0
    assert set(exchange["attrs"]["bucket_under_grads"].split(",")) <= {"0", "1"}
    assert exchange["attrs"]["pack_bytes"] == exchange["attrs"]["h2d_bytes"] == 4 * n_params()
    # nothing per bucket, and at most 12 new entries a step
    assert not [s for s in spans if s["name"].startswith("exchange.")]
    assert sum(1 for s in spans if s["name"] in STEP_SPANS) <= 12 * 3


def test_a_vetoed_step_has_a_commit_and_no_apply(spans_train_step, monkeypatch):
    tracing.TRACER.clear()
    run_steps(spans_train_step, 3, monkeypatch, veto_step=1)
    spans = tracing.TRACER.recent()
    steps = [s for s in spans if s["name"] == "step"]
    # the step after a veto is the vetoed step again: nothing was committed
    assert [s["trace_id"].split(":")[1] for s in steps] == ["0", "1", "1"]
    assert [s["attrs"]["committed"] for s in steps] == [True, False, True]
    for step in steps:
        children = [s["name"] for s in spans if s.get("parent_id") == step["span_id"]]
        # every piece once — a step computes its gradients once, whatever the
        # vote says — and the update only behind a commit
        want = set(STEP_SPANS[1:]) - {"commit.prepare"}
        if not step["attrs"]["committed"]:
            want -= {"apply"}
        assert sorted(children) == sorted(want)


def test_programs_and_scopes_have_stable_names(spans_train_step):
    ts = spans_train_step
    params = ts.init_params(jax.random.PRNGKey(0))
    opt = ts.init_opt(params)
    tokens = ts.shard_batch(jnp.zeros((2, 16), jnp.int32))
    with jax.set_mesh(ts.mesh):
        grads = ts._value_and_grad.lower(params, tokens).as_text(debug_info=True)
        apply = ts._apply.lower(params, opt, params).as_text(debug_info=True)
        fused = ts._fused.lower(params, opt, tokens).as_text(debug_info=True)
    assert "module @jit_tft_grads" in grads
    assert "module @jit_tft_apply" in apply
    assert "module @jit_tft_fused" in fused
    assert scopes(grads) == {"embed", "attn", "ffn", "head_loss"}
    assert scopes(apply) == {"optimizer"}
    assert scopes(fused) == {"embed", "attn", "ffn", "head_loss", "optimizer"}


def scopes(text):
    found = set()
    for loc in re.findall(r'loc\("([^"]*)"', text):
        # a scope is a component of the op's path: attn/add, jvp(embed)/jit
        found.update(re.findall(r"(?:^|[/(])(embed|attn|ffn|moe|head_loss|optimizer)(?=[/)])", loc))
    return found


def test_every_link_of_the_chain_is_a_module_called_tft_grads(spans_train_step):
    """``grads`` of this stack is a chain of programs (head, a layer's, tail):
    each is a module ``jit_tft_grads`` — what sums a trace's ``XLA Modules``
    runs by that name sums the chain — under the scopes of its part of the
    model, and the update that takes the pieces is ``jit_tft_apply``."""
    ts = spans_train_step
    params = ts.init_params(jax.random.PRNGKey(0))
    opt = ts.init_opt(params)
    tokens = ts.shard_batch(jnp.zeros((2, 16), jnp.int32))
    head, layer, tail = ts._chain
    with jax.set_mesh(ts.mesh):
        loss, stats, top, (dx, kept) = head(params, tokens)
        links = {
            "head": head.lower(params, tokens).as_text(debug_info=True),
            "layer": layer.lower(params["layers"], np.int32(0), kept, dx).as_text(debug_info=True),
            "tail": tail.lower(params["embed"], tokens, dx).as_text(debug_info=True),
        }
        apply = ts._apply_pieces.lower(params, opt, ts.grad_pieces(params)).as_text(debug_info=True)
    assert all("module @jit_tft_grads" in text for text in links.values())
    assert {name: scopes(text) for name, text in links.items()} == {
        "head": {"embed", "attn", "ffn", "head_loss"}, "layer": {"attn", "ffn"}, "tail": {"embed"},
    }
    assert "module @jit_tft_apply" in apply and scopes(apply) == {"optimizer"}
