"""Compressed client-update plane validation (ISSUE-9).

Three layers, each anchored to an oracle:

  1. kernels — interpret-mode Pallas ``topk_sparsify`` /
     ``quantize_i8`` / ``dequantize_i8`` / ``fedavg_agg_quality_i8``
     against their jnp references (ref.py), swept over ragged shapes
     and dtypes. Top-k selection must match ``lax.top_k`` over |x|
     exactly (ties to the lowest index); int8 values may differ by at
     most one quantization step from the oracle (the kernel's chunk-max
     reduction can land 1 ulp off the oracle's, which legitimately
     moves a value on a rounding boundary).
  2. codec — spec grammar, wire-byte accounting, round-trip error
     bounds (int8 error <= scale/2 per chunk; top-k exact on kept
     coordinates and zero elsewhere), quantize∘dequantize idempotence.
  3. round plane — ``compression="none"`` is bit-identical to the
     uncompressed scan, and a mid-period save→kill→restore with an
     active codec reproduces the remaining rounds exactly.
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import lifecycle
from repro.core.service import FLServiceProvider
from repro.fl.compression import (CompressionSpec, aggregate_compressed,
                                  bytes_per_client, compress, decompress,
                                  roundtrip)
from repro.kernels import ops, ref
from repro.kernels.compression import (fedavg_agg_quality_i8, quantize_i8,
                                       dequantize_i8, topk_sparsify)

SHAPES = [(13, 1000), (3, 130), (8, 50), (1, 7), (5, 257)]
DTYPES = [jnp.float32, jnp.bfloat16]


def rk(i):
    return jax.random.PRNGKey(i)


def scale_bound(x, chunk):
    """Per-element dequantization error bound: half an int8 step of the
    element's chunk scale (plus float slack)."""
    _, scales = ref.quantize_i8_ref(x.astype(jnp.float32), chunk)
    per_elem = jnp.repeat(scales, chunk, axis=1)[:, : x.shape[1]]
    return np.asarray(per_elem) * 0.5 * (1 + 1e-5) + 1e-8


def _magnitude_case(case):
    """``(x (K, P) f32, k)`` for one edge of the top-k selection."""
    rng = np.random.default_rng(len(case))
    sign = rng.choice(np.array([-1.0, 1.0]), size=(3, 1000))
    if case == "ties_straddle_tiles":
        # 60 magnitudes above the k-th, then 300 ties at it over every
        # 128-lane tile, with both signs
        x = rng.uniform(0.0, 0.5, (3, 1000))
        for row in x:
            row[rng.choice(1000, 360, replace=False)] = np.concatenate(
                [rng.uniform(2.0, 3.0, 60), np.ones(300)])
        return jnp.asarray(x * sign, jnp.float32), 100
    if case == "all_equal":
        return jnp.asarray(sign[:2, :700] * 1.5, jnp.float32), 300
    if case == "few_nonzero":
        x = np.zeros((3, 500))
        x[0, rng.choice(500, 20, replace=False)] = rng.normal(size=20)
        x[1, 0] = -1.0
        return jnp.asarray(x, jnp.float32), 64   # row 2 all zero
    if case == "k_is_width":
        return jnp.asarray(np.round(rng.normal(size=(2, 300)) * 2),
                           jnp.float32), 300
    if case == "k_not_whole_vregs":
        return jnp.asarray(np.round(rng.normal(size=(5, 1000)) * 2),
                           jnp.float32), 200
    if case == "signed_zeros":
        x = rng.choice(np.array([-0.0, 0.0, 1.0, -1.0]), size=(2, 400),
                       p=[0.35, 0.35, 0.15, 0.15])
        return jnp.asarray(x, jnp.float32), 150
    raise ValueError(case)


# ---------------------------------------------------------------------------
# 1. kernels vs oracles
# ---------------------------------------------------------------------------

class TestTopkSparsifyKernel:
    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("K,P", SHAPES)
    def test_matches_lax_topk_exactly(self, K, P, dtype):
        x = jax.random.normal(rk(0), (K, P), dtype)
        k = max(1, P // 10)
        vals, idx = topk_sparsify(x, k, interpret=True)
        rvals, ridx = ref.topk_sparsify_ref(x, k)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
        np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))

    @pytest.mark.parametrize("K,P,k", [(13, 1000, 100), (3, 2000, 7),
                                       (10, 1500, 300)])
    def test_tiled_rows_match_lax_topk(self, K, P, k):
        """Rows wider than one lane tile (block=128) go through the
        tile-frontier merge; tie-heavy magnitudes keep lowest-index
        order across tiles."""
        x = jnp.round(jax.random.normal(rk(8), (K, P)) * 3.0)
        vals, idx = topk_sparsify(x, k, block=128, interpret=True)
        rvals, ridx = ref.topk_sparsify_ref(x, k)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
        np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))

    @pytest.mark.parametrize("case", ["ties_straddle_tiles", "all_equal",
                                      "few_nonzero", "k_is_width",
                                      "k_not_whole_vregs", "signed_zeros"])
    def test_threshold_edge_cases_match_lax_topk(self, case):
        """The selection's edges at block=128: ties at the k-th
        magnitude split over tiles in lane order, a row of equal
        magnitudes, fewer nonzero entries than k, k of the whole row or
        of no whole number of vregs, and -0.0 beside +0.0."""
        x, k = _magnitude_case(case)
        vals, idx = topk_sparsify(x, k, block=128, interpret=True)
        rvals, ridx = ref.topk_sparsify_ref(x, k)
        np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))
        np.testing.assert_array_equal(np.asarray(vals), np.asarray(rvals))
        np.testing.assert_array_equal(np.signbit(np.asarray(vals)),
                                      np.signbit(np.asarray(rvals)))

    def test_tie_break_is_lowest_index(self):
        # constant-|x| rows: selection must be the first k lanes, in
        # order, with the original signs — deterministic across runs
        x = jnp.array([[1.0, -1.0, 1.0, -1.0, 1.0, -1.0]])
        for k in (1, 3, 6):
            vals, idx = topk_sparsify(x, k, interpret=True)
            np.testing.assert_array_equal(np.asarray(idx[0]), np.arange(k))
            np.testing.assert_array_equal(np.asarray(vals),
                                          np.asarray(x[:, :k]))
            rvals, ridx = ref.topk_sparsify_ref(x, k)
            np.testing.assert_array_equal(np.asarray(idx), np.asarray(ridx))

    def test_k_clamps_to_row_width(self):
        x = jax.random.normal(rk(1), (2, 5))
        vals, idx = topk_sparsify(x, 9, interpret=True)
        assert vals.shape == (2, 5)
        # every column selected exactly once
        assert sorted(np.asarray(idx[0]).tolist()) == list(range(5))

    def test_signed_values_kept(self):
        x = jnp.array([[-3.0, 1.0, 2.0, -0.5]])
        vals, idx = topk_sparsify(x, 2, interpret=True)
        np.testing.assert_array_equal(np.asarray(idx[0]), [0, 2])
        np.testing.assert_array_equal(np.asarray(vals[0]), [-3.0, 2.0])


class TestQuantizeI8Kernel:
    @pytest.mark.parametrize("dtype", DTYPES)
    @pytest.mark.parametrize("K,P", SHAPES)
    @pytest.mark.parametrize("chunk", [64, 256])
    def test_matches_oracle_within_one_step(self, K, P, chunk, dtype):
        x = jax.random.normal(rk(2), (K, P), dtype)
        v, s = quantize_i8(x, chunk=chunk, interpret=True)
        rv, rs = ref.quantize_i8_ref(x, chunk)
        assert v.dtype == jnp.int8 and v.shape == (K, P)
        np.testing.assert_allclose(np.asarray(s), np.asarray(rs), rtol=2e-7)
        # the chunk-max reduction may differ by 1 ulp between kernel
        # and oracle, which can move a value across a rounding
        # boundary: one int8 step is the contract
        diff = np.abs(np.asarray(v, np.int32) - np.asarray(rv, np.int32))
        assert diff.max() <= 1

    @pytest.mark.parametrize("K,P", [(3, 130), (5, 257)])
    def test_dequantize_matches_oracle(self, K, P):
        x = jax.random.normal(rk(3), (K, P))
        v, s = ref.quantize_i8_ref(x, 64)      # shared payload
        d = dequantize_i8(v, s, chunk=64, interpret=True)
        rd = ref.dequantize_i8_ref(v, s, 64)
        np.testing.assert_allclose(np.asarray(d), np.asarray(rd), rtol=2e-7)

    @pytest.mark.parametrize("K,P", [(10, 1000), (3, 1300)])
    def test_multi_tile_rows(self, K, P):
        """More than 128 chunks per row: several (8, 128 * chunk) lane
        tiles, a ragged last tile, and rows padded to 8."""
        x = jax.random.normal(rk(9), (K, P))
        v, s = quantize_i8(x, chunk=4, interpret=True)
        rv, rs = ref.quantize_i8_ref(x, 4)
        assert s.shape == rs.shape == (K, -(-P // 4))
        np.testing.assert_allclose(np.asarray(s), np.asarray(rs), rtol=2e-7)
        diff = np.abs(np.asarray(v, np.int32) - np.asarray(rv, np.int32))
        assert diff.max() <= 1
        d = dequantize_i8(rv, rs, chunk=4, interpret=True)
        np.testing.assert_allclose(np.asarray(d),
                                   np.asarray(ref.dequantize_i8_ref(rv, rs, 4)),
                                   rtol=2e-7)
        w = jax.nn.softmax(jax.random.normal(rk(10), (K,)))
        out = fedavg_agg_quality_i8(rv, rs, w, chunk=4, interpret=True)
        expect = ref.fedavg_agg_quality_i8_ref(rv, rs, w, 4)
        for got, want in zip(out, expect):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-4)

    def test_zero_chunks_are_exact(self):
        x = jnp.zeros((2, 100))
        v, s = quantize_i8(x, chunk=32, interpret=True)
        assert np.asarray(v).max() == 0 and np.asarray(s).max() == 0.0
        d = dequantize_i8(v, s, chunk=32, interpret=True)
        assert np.asarray(d).max() == 0.0

    def test_extremes_saturate_at_127(self):
        x = jnp.array([[127.0, -127.0, 63.5, 0.0]])
        v, s = quantize_i8(x, chunk=4, interpret=True)
        np.testing.assert_array_equal(np.asarray(v[0]), [127, -127, 64, 0])
        assert float(s[0, 0]) == pytest.approx(1.0)


class TestAggQualityI8Kernel:
    @pytest.mark.parametrize("K,P", [(13, 1000), (3, 130), (8, 50)])
    @pytest.mark.parametrize("chunk", [64, 256])
    def test_matches_oracle(self, K, P, chunk):
        x = jax.random.normal(rk(4), (K, P))
        w = jax.nn.softmax(jax.random.normal(rk(5), (K,)))
        v, s = ref.quantize_i8_ref(x, chunk)   # shared payload
        out = fedavg_agg_quality_i8(v, s, w, chunk=chunk, interpret=True)
        expect = ref.fedavg_agg_quality_i8_ref(v, s, w, chunk)
        for got, want in zip(out, expect):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-4, atol=1e-4)

    def test_equals_uncompressed_quality_on_decoded(self):
        # the fused kernel must agree with dequantize -> the existing
        # fedavg_agg_quality oracle (same decoded updates)
        K, P = 6, 200
        x = jax.random.normal(rk(6), (K, P))
        w = jnp.full((K,), 1.0 / K)
        v, s = ref.quantize_i8_ref(x, 64)
        u = ref.dequantize_i8_ref(v, s, 64)
        agg, dots, sq, asq = fedavg_agg_quality_i8(v, s, w, chunk=64,
                                                   interpret=True)
        ragg, rdots, rsq, rasq = ref.fedavg_agg_quality_ref(u, w)
        np.testing.assert_allclose(np.asarray(agg), np.asarray(ragg),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dots), np.asarray(rdots),
                                   rtol=1e-4)
        np.testing.assert_allclose(np.asarray(sq), np.asarray(rsq),
                                   rtol=1e-4)
        np.testing.assert_allclose(float(asq), float(rasq), rtol=1e-4)

    def test_dispatch_layer_routes_to_oracle_on_cpu(self):
        # interpret=None on CPU must take the jnp reference path and
        # agree with the interpret-mode kernel
        K, P = 4, 90
        x = jax.random.normal(rk(7), (K, P))
        w = jnp.full((K,), 0.25)
        v, s = ops.quantize_i8(x, chunk=32)            # oracle route
        vi, si = quantize_i8(x, chunk=32, interpret=True)
        np.testing.assert_allclose(np.asarray(s), np.asarray(si), rtol=2e-7)
        out = ops.fedavg_agg_quality_i8(v, s, w, chunk=32)
        ki = fedavg_agg_quality_i8(v, s, w, chunk=32, interpret=True)
        for a, b in zip(out, ki):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# 2. codec layer
# ---------------------------------------------------------------------------

class TestSpecGrammar:
    @pytest.mark.parametrize("text,kind,frac,chunk", [
        (None, "none", 0.0, 256),
        ("", "none", 0.0, 256),
        ("none", "none", 0.0, 256),
        ("int8", "int8", 0.0, 256),
        ("int8@chunk=64", "int8", 0.0, 64),
        ("topk:0.1", "topk", 0.1, 256),
        ("topk:0.05+int8", "topk_int8", 0.05, 256),
        ("topk:0.05+int8@chunk=128", "topk_int8", 0.05, 128),
    ])
    def test_parse(self, text, kind, frac, chunk):
        spec = CompressionSpec.parse(text)
        assert (spec.kind, spec.topk_frac, spec.chunk) == (kind, frac, chunk)
        # describe() round-trips through parse()
        assert CompressionSpec.parse(spec.describe()) == spec

    @pytest.mark.parametrize("bad", ["gzip", "topk:0", "topk:1.5",
                                     "int8@block=4", "int8@chunk=0"])
    def test_rejects(self, bad):
        with pytest.raises(ValueError):
            CompressionSpec.parse(bad)
        with pytest.raises(TypeError):
            CompressionSpec.parse(123)

    def test_bytes_accounting(self):
        p = 1000
        assert bytes_per_client(CompressionSpec.parse(None), p) == 4 * p
        assert bytes_per_client(CompressionSpec.parse("int8"), p) == \
            p + 4 * 4                                     # 4 chunks of 256
        assert bytes_per_client(CompressionSpec.parse("topk:0.1"), p) == \
            8 * 100                                       # f32 + i32 per kept
        spec = CompressionSpec.parse("topk:0.05+int8")
        assert bytes_per_client(spec, p) == 50 + 4 * 1 + 4 * 50
        # the ratios the bench asserts: >= 8x for the quantized-sparse
        assert 4 * p / bytes_per_client(spec, p) > 8

    def test_k_for_clamps(self):
        spec = CompressionSpec.parse("topk:0.1")
        assert spec.k_for(1000) == 100
        assert spec.k_for(5) == 1
        assert spec.k_for(0) == 0 or spec.k_for(1) == 1


class TestRoundtripBounds:
    @pytest.mark.parametrize("K,P", [(4, 357), (2, 64), (3, 1000)])
    def test_int8_error_bounded_by_half_step(self, K, P):
        x = jax.random.normal(rk(8), (K, P))
        y = roundtrip(x, CompressionSpec.parse("int8@chunk=64"))
        err = np.abs(np.asarray(x) - np.asarray(y))
        assert (err <= scale_bound(x, 64)).all()

    def test_topk_exact_on_kept_zero_elsewhere(self):
        K, P = 3, 200
        x = jax.random.normal(rk(9), (K, P))
        spec = CompressionSpec.parse("topk:0.1")
        payload = compress(x, spec)
        y = np.asarray(decompress(payload, spec, P))
        idx = np.asarray(payload["indices"])
        for r in range(K):
            kept = idx[r]
            np.testing.assert_array_equal(y[r, kept],
                                          np.asarray(x)[r, kept])
            mask = np.ones(P, bool)
            mask[kept] = False
            assert (y[r, mask] == 0).all()

    def test_quantize_dequantize_idempotent(self):
        # q(deq(q(x))) == q(x): a dequantized payload re-encodes to
        # itself (the grid values are fixed points of the codec)
        x = jax.random.normal(rk(10), (4, 300))
        v1, s1 = ops.quantize_i8(x, chunk=64)
        d1 = ops.dequantize_i8(v1, s1, chunk=64)
        v2, s2 = ops.quantize_i8(d1, chunk=64)
        d2 = ops.dequantize_i8(v2, s2, chunk=64)
        np.testing.assert_allclose(np.asarray(d1), np.asarray(d2),
                                   rtol=1e-6, atol=1e-7)

    @pytest.mark.parametrize("text", ["int8", "topk:0.25", "topk:0.25+int8"])
    def test_aggregate_compressed_matches_decoded_oracle(self, text):
        K, P = 5, 260
        spec = CompressionSpec.parse(text)
        x = jax.random.normal(rk(11), (K, P))
        w = jax.nn.softmax(jax.random.normal(rk(12), (K,)))
        agg, dots, sq, asq = aggregate_compressed(x, w, spec)
        decoded = roundtrip(x, spec)
        ragg, rdots, rsq, rasq = ref.fedavg_agg_quality_ref(decoded, w)
        np.testing.assert_allclose(np.asarray(agg), np.asarray(ragg),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(np.asarray(dots), np.asarray(rdots),
                                   rtol=1e-4, atol=1e-5)
        np.testing.assert_allclose(float(asq), float(rasq), rtol=1e-4)


# ---------------------------------------------------------------------------
# 3. round plane: bit-identity and compressed resume
# ---------------------------------------------------------------------------

def _bundle(compression=None, server_opt=None, seed=0):
    from repro.fl.transformer_task import make_transformer_fl
    return make_transformer_fl(n_clients=10, n_train=100, n_test=30,
                               seq_len=8, seed=seed, compression=compression,
                               server_opt=server_opt)


def _task(compression=None, max_rounds=4, round_chunk=2):
    return lifecycle.TaskRequest(budget=200.0, subset_size=4, subset_delta=2,
                                 x_star=2, max_periods=3,
                                 max_rounds=max_rounds,
                                 round_chunk=round_chunk, seed=0,
                                 compression=compression)


def _leaves(tree):
    return [np.asarray(x) for x in jax.tree_util.tree_leaves(tree)]


class TestRoundPlane:
    def test_none_is_bit_identical(self):
        # compression="none" must produce the exact trace of the
        # uncompressed scan — same jaxpr path, same bits out
        runs = {}
        for comp in (None, "none"):
            b = _bundle(compression=comp)
            sp = FLServiceProvider(b["pool"])
            st = lifecycle.submit(sp, _task(compression=comp))
            st, ev = lifecycle.drain(sp, st, b["trainer"])
            runs[comp] = (_leaves(b["trainer"].params), ev)
        for a, b in zip(runs[None][0], runs["none"][0]):
            np.testing.assert_array_equal(a, b)
        assert [e.subset for e in runs[None][1]] == \
            [e.subset for e in runs["none"][1]]
        # no codec -> no bytes column in the round metrics
        assert all("bytes" not in e.metrics for e in runs[None][1])

    def test_bytes_metric_matches_accounting(self):
        comp = "topk:0.25+int8"
        b = _bundle(compression=comp)
        sp = FLServiceProvider(b["pool"])
        st = lifecycle.submit(sp, _task(compression=comp))
        st, ev = lifecycle.drain(sp, st, b["trainer"])
        spec = CompressionSpec.parse(comp)
        flat_p = sum(int(np.prod(np.shape(x)))
                     for x in jax.tree_util.tree_leaves(b["trainer"].params))
        per_client = bytes_per_client(spec, flat_p)
        hist = [h for h in b["trainer"].history if "bytes" in h]
        assert hist, "compressed rounds must report a bytes column"
        for h in hist:
            n_arrived = h.get("arrived", None)
            assert h["bytes"] % per_client == 0
            assert h["bytes"] > 0

    @pytest.mark.parametrize("comp", ["int8", "topk:0.25+int8"])
    def test_compressed_resume_reproduces_rounds(self, comp, tmp_path):
        # reference: straight-through run
        b1 = _bundle(compression=comp)
        p1 = FLServiceProvider(b1["pool"])
        s1 = lifecycle.submit(p1, _task(compression=comp, max_rounds=6,
                                        round_chunk=1))
        s1, ref_ev = lifecycle.drain(p1, s1, b1["trainer"])

        # run 2: stop after 3 rounds, checkpoint with trainer state
        b2 = _bundle(compression=comp)
        p2 = FLServiceProvider(b2["pool"])
        s2 = lifecycle.submit(p2, _task(compression=comp, max_rounds=6,
                                        round_chunk=1))
        got = []
        while len(got) < 3:
            s2, ev = lifecycle.step(p2, s2, b2["trainer"])
            got.extend(ev)
        path = os.path.join(tmp_path, "mid.ckpt")
        got += lifecycle.save_state(path, s2, flush=True,
                                    trainer=b2["trainer"])

        # "fresh process": new trainer, restored control + model state
        s3 = lifecycle.load_state(path)
        assert s3.task.compression == comp
        b3 = _bundle(compression=comp)
        assert lifecycle.restore_trainer_state(s3, b3["trainer"])
        p3 = FLServiceProvider(b3["pool"])
        s3, post = lifecycle.drain(p3, s3, b3["trainer"])

        rounds = got + post
        assert len(rounds) == len(ref_ev)
        for a, b in zip(rounds, ref_ev):
            assert (a.period, a.round_index, a.subset) == \
                (b.period, b.round_index, b.subset)
            assert a.nid == b.nid
        for x, y in zip(_leaves(b1["trainer"].params),
                        _leaves(b3["trainer"].params)):
            np.testing.assert_array_equal(x, y)

    def test_server_opt_state_rides_checkpoint(self, tmp_path):
        b = _bundle(compression="int8", server_opt="fedyogi")
        sp = FLServiceProvider(b["pool"])
        st = lifecycle.submit(sp, _task(compression="int8"))
        st, _ = lifecycle.drain(sp, st, b["trainer"])
        path = os.path.join(tmp_path, "opt.ckpt")
        lifecycle.save_state(path, st, trainer=b["trainer"])
        back = lifecycle.load_state(path)
        b2 = _bundle(compression="int8", server_opt="fedyogi")
        assert lifecycle.restore_trainer_state(back, b2["trainer"])
        for x, y in zip(_leaves(b["trainer"].opt_state),
                        _leaves(b2["trainer"].opt_state)):
            np.testing.assert_array_equal(x, y)
