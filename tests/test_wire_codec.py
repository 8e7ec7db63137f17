"""Wire-codec + error-feedback tests (docs/wire_plane.md).

Covers the PR 6 satellite checklist: quantize/dequantize round-trip
bounds, error-feedback residual carry across steps (the sum of applied
updates converges to the sum of true gradients), bit-identity of the
decoded average across ranks on BOTH wire planes, commit-lineage
rollback, and heal/checkpoint round-trip of accumulator state. The
tiny-size smoke tests keep the compression path exercised in tier-1 on
every run.
"""

import struct
from concurrent.futures import ThreadPoolExecutor
from datetime import timedelta

import numpy as np
import pytest

from torchft_tpu.wire_codec import (
    Bf16Codec,
    ErrorFeedback,
    F32Codec,
    Int8Codec,
    LowRankErrorFeedback,
    get_codec,
    lowrank_basis,
    lowrank_compress,
    lowrank_decompress,
    lowrank_eligible,
)


def _roundtrip(codec, arr):
    out = arr.copy()
    codec.roundtrip(out)
    return out


class TestCodecs:
    def test_registry(self):
        assert isinstance(get_codec(None), F32Codec)
        assert isinstance(get_codec("f32"), F32Codec)
        assert isinstance(get_codec("bfloat16"), Bf16Codec)
        assert isinstance(get_codec("int8"), Int8Codec)
        with pytest.raises(ValueError):
            get_codec("fp4")

    def test_f32_roundtrip_exact(self):
        rng = np.random.default_rng(0)
        a = rng.standard_normal(1001).astype(np.float32)
        assert np.array_equal(_roundtrip(F32Codec(), a), a)

    def test_bf16_roundtrip_bound(self):
        rng = np.random.default_rng(1)
        a = rng.standard_normal(4096).astype(np.float32)
        got = _roundtrip(Bf16Codec(), a)
        # bf16 keeps 8 mantissa bits: relative error <= 2^-8
        np.testing.assert_allclose(got, a, rtol=2**-8, atol=1e-30)
        # matches the numpy astype semantics the native plane mirrors
        import ml_dtypes

        np.testing.assert_array_equal(
            got, a.astype(ml_dtypes.bfloat16).astype(np.float32)
        )

    def test_int8_roundtrip_bound(self):
        rng = np.random.default_rng(2)
        a = (rng.standard_normal(4096) * 3.7).astype(np.float32)
        got = _roundtrip(Int8Codec(), a)
        amax = float(np.abs(a).max())
        # half a quantization step, plus fp slack
        assert float(np.abs(got - a).max()) <= amax / 127.0 * 0.5 * 1.01

    def test_int8_wire_format(self):
        codec = Int8Codec()
        a = np.array([0.0, 127.0, -127.0, 63.5], dtype=np.float32)
        w = bytes(codec.encode_into(a))
        assert len(w) == 4 + a.size
        (scale,) = struct.unpack("<f", w[:4])
        assert scale == pytest.approx(1.0)
        q = np.frombuffer(w[4:], dtype=np.int8)
        # 63.5/1.0 rounds half-to-even -> 64
        assert q.tolist() == [0, 127, -127, 64]

    def test_int8_roundtrip_idempotent(self):
        # projecting twice must land on the same grid point: the error-
        # feedback contract (apply() projects, the wire re-encodes)
        rng = np.random.default_rng(3)
        a = rng.standard_normal(512).astype(np.float32)
        codec = Int8Codec()
        once = _roundtrip(codec, a)
        twice = _roundtrip(codec, once)
        np.testing.assert_array_equal(once, twice)

    def test_int8_nan_propagates(self):
        codec = Int8Codec()
        a = np.array([1.0, np.nan, 2.0], dtype=np.float32)
        got = _roundtrip(codec, a)
        assert np.isnan(got).all(), "NaN must poison the chunk loudly"
        a = np.array([1.0, np.inf], dtype=np.float32)
        assert np.isnan(_roundtrip(codec, a)).all()

    def test_int8_zero_chunk(self):
        codec = Int8Codec()
        a = np.zeros(17, dtype=np.float32)
        np.testing.assert_array_equal(_roundtrip(codec, a), a)

    def test_empty_chunk(self):
        for codec in (Bf16Codec(), Int8Codec()):
            a = np.empty(0, dtype=np.float32)
            codec.roundtrip(a)  # must not raise

    def test_wire_nbytes(self):
        assert F32Codec().wire_nbytes(10) == 40
        assert Bf16Codec().wire_nbytes(10) == 20
        assert Int8Codec().wire_nbytes(10) == 14


class TestErrorFeedback:
    def test_rejects_exact_codec(self):
        with pytest.raises(ValueError):
            ErrorFeedback(F32Codec())

    def test_residual_carry_converges(self):
        """EF-SGD invariant: sum(applied_t) = sum(g_t) − e_T, so the
        averaged applied update converges to the true gradient at 1/T
        while naive quantization keeps a constant bias."""
        rng = np.random.default_rng(4)
        g = (rng.standard_normal(256) * 0.01).astype(np.float32)
        ef = ErrorFeedback(Int8Codec())
        naive_codec = Int8Codec()
        applied_sum = np.zeros_like(g)
        naive_sum = np.zeros_like(g)
        steps = 64
        for _ in range(steps):
            buf = g.copy()
            ef.apply("b0_256", buf)
            ef.commit()
            applied_sum += buf
            nb = g.copy()
            naive_codec.roundtrip(nb)
            naive_sum += nb
        amax = float(np.abs(g).max())
        ef_err = float(np.abs(applied_sum / steps - g).max())
        naive_err = float(np.abs(naive_sum / steps - g).max())
        # EF's residual is bounded by ONE step's quantization error
        assert ef_err <= amax / 127.0 / steps * 2.0
        # and it beats the naive bias by an order of magnitude here
        assert ef_err < naive_err / 5.0

    def test_rollback_discards_pending_only(self):
        g = np.linspace(-1, 1, 64, dtype=np.float32)
        ef = ErrorFeedback(Int8Codec())
        buf = g.copy()
        ef.apply("k", buf)
        ef.commit()
        acc_after_commit = ef.state_dict()["acc"]["k"].copy()
        buf2 = g.copy()
        ef.apply("k", buf2)
        assert ef.pending_keys() == ("k",)
        ef.rollback()
        assert ef.pending_keys() == ()
        np.testing.assert_array_equal(
            ef.state_dict()["acc"]["k"], acc_after_commit
        )

    def test_size_change_drops_stale_residual(self):
        ef = ErrorFeedback(Int8Codec())
        buf = np.ones(8, dtype=np.float32)
        ef.apply("k", buf)
        ef.commit()
        big = np.ones(16, dtype=np.float32)
        ef.apply("k", big)  # must not mis-add the 8-elem residual
        ef.commit()
        assert ef.state_dict()["acc"]["k"].size == 16

    def test_state_dict_roundtrip(self):
        ef = ErrorFeedback(Int8Codec())
        buf = np.linspace(0, 1, 32, dtype=np.float32)
        ef.apply("k", buf)
        ef.commit()
        state = ef.state_dict()
        assert state["codec"] == "int8"
        ef2 = ErrorFeedback(Int8Codec())
        ef2.load_state_dict(state)
        np.testing.assert_array_equal(
            ef2.state_dict()["acc"]["k"], state["acc"]["k"]
        )

    def test_codec_mismatch_drops_accumulators(self):
        ef = ErrorFeedback(Int8Codec())
        buf = np.ones(4, dtype=np.float32)
        ef.apply("k", buf)
        ef.commit()
        ef2 = ErrorFeedback(Bf16Codec())
        ef2.load_state_dict(ef.state_dict())
        assert ef2.state_dict()["acc"] == {}

    def test_pending_excluded_from_state_dict(self):
        ef = ErrorFeedback(Int8Codec())
        buf = np.ones(4, dtype=np.float32)
        ef.apply("k", buf)  # staged, not committed
        assert ef.state_dict()["acc"] == {}


# ---------------------------------------------------------------------------
# optimizer integration (stub manager; the live 2-group path is covered
# by the faultmatrix kill_streamed_bucket / torn_compressed_frame runs)
# ---------------------------------------------------------------------------


class _WireStubManager:
    """Single-group manager stand-in reporting a lossy wire codec."""

    def __init__(self, commits, codec="int8"):
        self._commits = list(commits)
        self._codec = codec
        self._load = None
        self._save = None

    def wire_codec(self):
        return self._codec

    def set_state_dict_fns(self, load, save):
        self._load, self._save = load, save

    def start_quorum(self, **kw):
        pass

    def device_data_plane(self):
        return False

    def is_participating(self):
        return True

    def num_participants(self):
        return 1

    def errored(self):
        return None

    def allreduce_many(self, arrays):
        from torchft_tpu.futures import Future

        return Future.completed(arrays)

    def should_commit(self):
        return self._commits.pop(0)


class TestManagedOptimizerEF:
    def _opt(self, commits, codec="int8"):
        import optax

        from torchft_tpu.optim import ManagedOptimizer

        mgr = _WireStubManager(commits, codec=codec)
        opt = ManagedOptimizer(mgr, optax.sgd(1.0))
        opt.init({"w": np.zeros(64, dtype=np.float32)})
        return opt

    def test_auto_enabled_for_lossy_codec(self):
        assert self._opt([True]).error_feedback is not None
        assert self._opt([True], codec="f32").error_feedback is None

    def test_env_veto(self, monkeypatch):
        monkeypatch.setenv("TORCHFT_WIRE_EF", "0")
        assert self._opt([True]).error_feedback is None

    def test_commit_promotes_abort_rolls_back(self):
        opt = self._opt([True, False, True])
        g = {"w": np.full(64, 0.013, dtype=np.float32)}
        opt.step({k: v.copy() for k, v in g.items()})  # committed
        ef = opt.error_feedback
        acc1 = ef.state_dict()["acc"]
        assert acc1, "committed step must promote its residual"
        w1 = opt.params["w"].copy()
        opt.step({k: v.copy() for k, v in g.items()})  # aborted
        np.testing.assert_array_equal(np.asarray(opt.params["w"]), w1)
        for k, v in ef.state_dict()["acc"].items():
            np.testing.assert_array_equal(v, acc1[k])
        opt.step({k: v.copy() for k, v in g.items()})  # committed again
        assert not np.array_equal(np.asarray(opt.params["w"]), w1)

    def test_heal_roundtrip_carries_accumulators(self):
        opt = self._opt([True])
        g = {"w": np.full(64, 0.007, dtype=np.float32)}
        opt.step({k: v.copy() for k, v in g.items()})
        state = opt.state_dict()
        assert "ef" in state and state["ef"]["acc"]
        opt2 = self._opt([True])
        opt2.load_state_dict(state)
        got = opt2.error_feedback.state_dict()["acc"]
        want = state["ef"]["acc"]
        assert set(got) == set(want)
        for k in want:
            np.testing.assert_array_equal(got[k], want[k])

    def test_heal_adopts_ef_state_before_lazy_creation(self):
        # a proxied backend reports "f32" until its first configure: the
        # EF instance doesn't exist yet when a heal lands — the healed
        # accumulators must be ADOPTED (created from the state's own
        # codec), not silently dropped
        donor = self._opt([True])
        g = {"w": np.full(64, 0.007, dtype=np.float32)}
        donor.step({k: v.copy() for k, v in g.items()})
        state = donor.state_dict()
        healer = self._opt([True], codec="f32")  # plane not lossy YET
        assert healer.error_feedback is None
        healer.load_state_dict(state)
        assert healer.error_feedback is not None
        got = healer.error_feedback.state_dict()["acc"]
        for k, v in state["ef"]["acc"].items():
            np.testing.assert_array_equal(got[k], v)

    def test_heal_without_ef_state_starts_clean(self):
        opt = self._opt([True, True])
        g = {"w": np.full(64, 0.007, dtype=np.float32)}
        opt.step({k: v.copy() for k, v in g.items()})
        opt.load_state_dict(
            {"params": opt.params, "opt_state": opt.opt_state}
        )
        assert opt.error_feedback.state_dict()["acc"] == {}


# ---------------------------------------------------------------------------
# tiny-size tier-1 smoke: the compressed wire exercised on every run,
# bit-identity asserted on both planes
# ---------------------------------------------------------------------------


def _ring_world(store, world, codec, prefix, **kw):
    from torchft_tpu.collectives import CollectivesTcp, ReduceOp

    colls = [
        CollectivesTcp(
            hostname="localhost",
            timeout=timedelta(seconds=15),
            wire_dtype=codec,
            **kw,
        )
        for _ in range(world)
    ]

    def start(rank):
        colls[rank].configure(f"{store.address()}/{prefix}", rank, world)
        rng = np.random.default_rng(100 + rank)
        a = rng.standard_normal(10007).astype(np.float32)
        ref = a.copy()
        out = colls[rank].allreduce([a], ReduceOp.AVG).wait(
            timedelta(seconds=20)
        )
        info = (colls[rank].plane_info(), colls[rank].wire_codec())
        colls[rank].shutdown()
        return ref, out[0], info

    with ThreadPoolExecutor(max_workers=world) as ex:
        return list(ex.map(start, range(world)))


@pytest.fixture()
def store():
    from torchft_tpu.store import StoreServer

    s = StoreServer()
    yield s
    s.shutdown()


class TestCompressedWireSmoke:
    @pytest.mark.parametrize("codec", ["int8", "bfloat16"])
    def test_python_ring_bit_identical(self, store, monkeypatch, codec):
        monkeypatch.setenv("TORCHFT_NATIVE_PLANE", "0")
        outs = _ring_world(store, 3, codec, f"pyring{codec}")
        assert outs[0][2] == ("python-ring", codec)
        for _, got, _info in outs[1:]:
            np.testing.assert_array_equal(got, outs[0][1])
        expect = np.mean([r for r, _, _ in outs], axis=0)
        rtol = 0.02 if codec == "int8" else 0.01
        np.testing.assert_allclose(
            outs[0][1], expect, rtol=rtol, atol=rtol
        )

    @pytest.mark.parametrize("codec", ["int8", "bfloat16"])
    def test_native_striped_bit_identical(self, store, monkeypatch, codec):
        monkeypatch.setenv("TORCHFT_DP_CMA", "0")
        outs = _ring_world(store, 3, codec, f"native{codec}")
        assert outs[0][2] == ("tcp-striped", codec)
        for _, got, _info in outs[1:]:
            np.testing.assert_array_equal(got, outs[0][1])
        expect = np.mean([r for r, _, _ in outs], axis=0)
        rtol = 0.02 if codec == "int8" else 0.01
        np.testing.assert_allclose(
            outs[0][1], expect, rtol=rtol, atol=rtol
        )

    def test_cma_bypasses_codec(self, store):
        # same-host CMA moves exact f32: wire_codec() must say so, which
        # is also what disables error-feedback compensation per step
        outs = _ring_world(store, 2, "int8", "cmacodec")
        assert outs[0][2] == ("cma", "f32")
        expect = (outs[0][0] + outs[1][0]) / 2.0
        np.testing.assert_allclose(outs[0][1], expect, rtol=1e-6)

    def test_env_codec_default(self, store, monkeypatch):
        monkeypatch.setenv("TORCHFT_WIRE_CODEC", "int8")
        monkeypatch.setenv("TORCHFT_NATIVE_PLANE", "0")
        outs = _ring_world(store, 2, None, "envcodec")
        assert outs[0][2] == ("python-ring", "int8")


# ---------------------------------------------------------------------------
# DiLoCo outer-step low-rank projection
# ---------------------------------------------------------------------------


class TestLowRank:
    def test_basis_deterministic(self):
        q1 = lowrank_basis((64, 32), 4, seed=7)
        q2 = lowrank_basis((64, 32), 4, seed=7)
        np.testing.assert_array_equal(q1, q2)
        assert q1.shape == (32, 4)
        # orthonormal columns
        np.testing.assert_allclose(
            q1.T @ q1, np.eye(4, dtype=np.float32), atol=1e-5
        )
        assert not np.array_equal(q1, lowrank_basis((64, 32), 4, seed=8))

    def test_eligibility(self):
        assert lowrank_eligible((64, 32), 4)
        assert not lowrank_eligible((64,), 4)
        assert not lowrank_eligible((64, 8), 4)  # min dim < 4r
        assert not lowrank_eligible((64, 32), 0)

    def test_projection_error_feedback_converges(self):
        """Residual carry across outer syncs: the averaged applied
        pseudogradient approaches the true one at 1/T even though each
        sync ships only a rank-4 projection."""
        rng = np.random.default_rng(9)
        m = rng.standard_normal((48, 32)).astype(np.float32)
        ef = LowRankErrorFeedback()
        applied_sum = np.zeros_like(m)
        one_shot = lowrank_decompress(
            lowrank_compress(m, lowrank_basis(m.shape, 4, seed=0)),
            lowrank_basis(m.shape, 4, seed=0),
        )
        steps = 48
        for t in range(steps):
            comp = ef.compensate("l0", m)
            q = lowrank_basis(m.shape, 4, seed=t)
            p = lowrank_compress(comp, q)
            approx = lowrank_decompress(p, q)
            ef.stage("l0", comp, approx)
            ef.commit()
            applied_sum += approx
        ef_err = float(np.abs(applied_sum / steps - m).max())
        shot_err = float(np.abs(one_shot - m).max())
        assert ef_err < shot_err / 3.0

    def test_rollback_contract(self):
        m = np.ones((16, 16), dtype=np.float32)
        ef = LowRankErrorFeedback()
        q = lowrank_basis(m.shape, 2, seed=0)
        comp = ef.compensate("l0", m)
        ef.stage("l0", comp, lowrank_decompress(lowrank_compress(comp, q), q))
        ef.rollback()
        np.testing.assert_array_equal(ef.compensate("l0", m), m)

    def test_diloco_state_dict_carries_lr_ef(self):
        import optax

        from torchft_tpu.local_sgd import DiLoCo

        class _Mgr(_WireStubManager):
            _use_async_quorum = False

        mgr = _Mgr([True, True], codec="f32")
        diloco = DiLoCo(mgr, optax.sgd(1.0), sync_every=1, outer_rank=2)
        params = {"w": np.zeros((32, 16), dtype=np.float32)}
        diloco.save(params)
        stepped = {
            "w": np.full((32, 16), 0.25, dtype=np.float32)
        }
        out = diloco.step(stepped)
        state = diloco.state_dict()
        assert state["outer_syncs"] == 1
        assert "lr_ef" in state and state["lr_ef"]["acc"]
        # the outer step descended toward the inner progress
        assert float(np.asarray(out["w"]).mean()) > 0.0
