"""Device-resident FL data plane tests: dense index pools, on-device
batch gather, chunked scan driver, and device-vs-legacy equivalence
(same seeds -> same schedule, masks, and metrics within tolerance)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.data.synthetic import make_classification_data
from repro.fl import device_data, run_fl_experiment
from repro.fl.partition import dense_index_pools, partition_labels
from repro.fl.round import flatten_stacked, make_fl_rounds_scan
from repro.fl.simulation import DeviceFLSim, FLClassificationSim, SimConfig
from repro.models import cnn


class TestDenseIndexPools:
    def test_padding_cycles_own_indices(self):
        parts = [np.array([5, 9, 2]), np.array([7]), np.array([1, 3])]
        pools, sizes = dense_index_pools(parts)
        assert pools.shape == (3, 3)
        np.testing.assert_array_equal(sizes, [3, 1, 2])
        np.testing.assert_array_equal(pools[0], [5, 9, 2])
        np.testing.assert_array_equal(pools[1], [7, 7, 7])   # cycled
        np.testing.assert_array_equal(pools[2], [1, 3, 1])   # cycled

    def test_explicit_cap_and_overflow(self):
        parts = [np.array([1, 2]), np.array([3])]
        pools, sizes = dense_index_pools(parts, cap=4)
        assert pools.shape == (2, 4)
        with pytest.raises(ValueError):
            dense_index_pools([np.arange(5)], cap=3)

    def test_empty_client(self):
        pools, sizes = dense_index_pools([np.array([], np.int64),
                                          np.array([4])])
        assert sizes[0] == 0 and sizes[1] == 1


class TestGather:
    def _staged(self):
        d = make_classification_data("mnist", 400, seed=0)
        parts = partition_labels(d.labels, 8, "type2", 10, seed=0)
        return d, parts, device_data.DeviceDataset.stage(d, parts)

    def test_samples_belong_to_client(self):
        d, parts, dd = self._staged()
        rows = jnp.array([0, 2, 5])
        _, pos_u = device_data.sample_positions(jax.random.PRNGKey(3), 7,
                                                3, 2, 16)
        idx = device_data.positions_to_indices(dd.pools, dd.sizes, rows, pos_u)
        for i, cid in enumerate([0, 2, 5]):
            assert set(np.asarray(idx[i]).ravel()) <= set(parts[cid])

    def test_batch_shapes_and_label_consistency(self):
        d, parts, dd = self._staged()
        rows = jnp.array([1, 3])
        _, pos_u = device_data.sample_positions(jax.random.PRNGKey(0), 0,
                                                2, 3, 4)
        batch = device_data.gather_batches(dd, rows, pos_u)
        assert batch["images"].shape == (2, 3, 4, 28, 28, 1)
        assert batch["labels"].shape == (2, 3, 4)
        idx = device_data.positions_to_indices(dd.pools, dd.sizes, rows, pos_u)
        np.testing.assert_array_equal(np.asarray(batch["labels"]),
                                      d.labels[np.asarray(idx)])

    def test_slot_keyed_draws_are_padding_invariant(self):
        mu4, pu4 = device_data.sample_positions(jax.random.PRNGKey(1), 5,
                                                4, 2, 8)
        mu9, pu9 = device_data.sample_positions(jax.random.PRNGKey(1), 5,
                                                9, 2, 8)
        np.testing.assert_array_equal(np.asarray(mu4), np.asarray(mu9[:4]))
        np.testing.assert_array_equal(np.asarray(pu4), np.asarray(pu9[:4]))

    def test_dropout_mask_keeps_a_client(self):
        active = jnp.array([1.0, 1.0, 1.0, 0.0])
        mask = device_data.dropout_mask(jnp.zeros(4), active, 0.5)
        np.testing.assert_array_equal(np.asarray(mask), [1, 0, 0, 0])
        # padded slots never survive
        mask = device_data.dropout_mask(jnp.ones(4), active, 0.0)
        assert float(mask[3]) == 0.0


class TestFlattenStacked:
    def test_roundtrip(self):
        tree = {"a": jnp.arange(12.0).reshape(3, 2, 2),
                "b": {"c": jnp.ones((3, 5))}}
        flat, unflatten = flatten_stacked(tree)
        assert flat.shape == (3, 9)
        back = unflatten(flat[1])
        np.testing.assert_array_equal(np.asarray(back["a"]),
                                      np.asarray(tree["a"][1]))
        np.testing.assert_array_equal(np.asarray(back["b"]["c"]),
                                      np.asarray(tree["b"]["c"][1]))


class TestFusedRoundQuality:
    def test_fused_round_matches_legacy_round(self):
        """make_fl_round(fused_quality=True) == two-pass path: same
        aggregate step and same q_t within f32 accumulate tolerance."""
        from repro.fl.round import make_fl_round
        cfg = cnn.MNIST_CNN
        params = cnn.init_params(cfg, jax.random.PRNGKey(0))
        d = make_classification_data("mnist", 4 * 2 * 8, seed=0)
        batches = {
            "images": jnp.asarray(d.images.reshape(4, 2, 8, 28, 28, 1)),
            "labels": jnp.asarray(d.labels.reshape(4, 2, 8))}
        w = jnp.full(4, 0.25)
        mask = jnp.array([1.0, 1.0, 0.0, 1.0])
        loss = lambda p, b: cnn.loss_fn(cfg, p, b)
        p_a, info_a = make_fl_round(loss, local_steps=2)(
            params, batches, w, mask)
        p_b, info_b = make_fl_round(loss, local_steps=2, fused_quality=True)(
            params, batches, w, mask)
        for a, b in zip(jax.tree_util.tree_leaves(p_a),
                        jax.tree_util.tree_leaves(p_b)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-5, atol=1e-6)
        np.testing.assert_allclose(np.asarray(info_a["q_values"]),
                                   np.asarray(info_b["q_values"]),
                                   rtol=1e-4, atol=1e-5)


class TestScanDriver:
    def _run(self, chunk_sizes, rounds=4, seed=0):
        """Drive the same 4-round schedule with the given chunking."""
        d = make_classification_data("mnist", 600, seed=seed)
        parts = partition_labels(d.labels, 8, "type1", 10, seed=seed)
        test = make_classification_data("mnist", 100, seed=seed + 1)
        sim = SimConfig(batch_size=8, local_steps=2, eval_every=1000,
                        dropout_rate=0.2, seed=seed)
        simul = DeviceFLSim(cnn.MNIST_CNN, d, parts, test, sim,
                            pad_subset_to=4)
        subsets = [[0, 1, 2], [3, 4, 5, 6], [7, 0, 1], [2, 3, 4]]
        weights = [np.full(len(s), 1.0 / len(s)) for s in subsets]
        results = []
        r = 0
        for cs in chunk_sizes:
            results += simul.run_rounds(r, subsets[r:r + cs],
                                        weights[r:r + cs])
            r += cs
        return simul, results

    def test_chunked_equals_per_round(self):
        """Chunked scan vs per-round dispatch: same seeds -> same masks
        and metrics (the chunking must be semantics-free)."""
        sim_a, res_a = self._run([1, 1, 1, 1])
        sim_b, res_b = self._run([4])
        for (ma, qa, meta), (mb, qb, metb) in zip(res_a, res_b):
            np.testing.assert_array_equal(ma, mb)
            np.testing.assert_allclose(qa, qb, rtol=1e-4, atol=1e-5)
            assert meta["loss"] == pytest.approx(metb["loss"], rel=1e-4)
        pa = jax.tree_util.tree_leaves(sim_a.params)
        pb = jax.tree_util.tree_leaves(sim_b.params)
        for a, b in zip(pa, pb):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=1e-4, atol=1e-5)

    def test_donated_params_still_usable(self):
        """chunk_fn donates params; the sim must keep only the output."""
        simul, _ = self._run([2, 2])
        assert np.isfinite(float(jax.tree_util.tree_leaves(
            simul.params)[0].sum()))


@pytest.mark.slow
class TestDeviceVsLegacyEquivalence:
    """The ISSUE-2 contract: same seeds -> same schedule, same dropout
    masks, and per-round metrics within tolerance between the legacy
    host-loop trainer and the device-resident chunked path."""

    def _experiment(self, data_plane, round_chunk=1):
        return run_fl_experiment(
            "mnist", "type2", n_clients=16, rounds=6, scheduler="mkp",
            n_train=900, n_test=200, subset_size=5,
            sim=SimConfig(batch_size=8, local_steps=2, eval_every=1000,
                          dropout_rate=0.1, seed=3),
            seed=3, data_plane=data_plane, round_chunk=round_chunk)

    def test_equivalence(self):
        host = self._experiment("host")
        dev = self._experiment("device", round_chunk=3)
        h_rounds, d_rounds = host["service"].rounds, dev["service"].rounds
        assert len(h_rounds) == len(d_rounds) == 6
        for hr, dr in zip(h_rounds, d_rounds):
            assert hr.subset == dr.subset          # same schedule
            assert hr.metrics["loss"] == pytest.approx(
                dr.metrics["loss"], rel=2e-2, abs=1e-3)
        # same dropout masks: reputation b_t histories must agree
        h_rep = host["service"].reputation
        d_rep = dev["service"].reputation
        assert set(h_rep) == set(d_rep)
        for cid in h_rep:
            assert h_rep[cid] == pytest.approx(d_rep[cid], abs=5e-2)

    def test_fast_impl_forward_bit_equal(self):
        """The device plane's CPU lowering matches the reference forward
        to f32 rounding: im2col + GEMM sums the conv taps in another
        order than XLA's conv, so logits of magnitude ~1 may differ by a
        few dozen ulp (measured 1.3e-6 on jax 0.9 CPU)."""
        cfg = cnn.MNIST_CNN
        params = cnn.init_params(cfg, jax.random.PRNGKey(0))
        x = jnp.asarray(np.random.default_rng(0)
                        .random((16, 28, 28, 1), dtype=np.float32))
        ref = cnn.forward(cfg, params, x, impl="reference")
        fast = cnn.forward(cfg, params, x, impl="fast")
        np.testing.assert_allclose(np.asarray(fast), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)


class TestPoolLowering:
    def test_reshape_pool_matches_reduce_window_odd_dims(self):
        """Both poolings agree (VALID truncation) on odd spatial dims."""
        from repro.models.cnn import _pool_reshape, _pool_window
        y = jnp.asarray(np.random.default_rng(1)
                        .random((2, 7, 9, 3), dtype=np.float32))
        np.testing.assert_array_equal(np.asarray(_pool_window(y)),
                                      np.asarray(_pool_reshape(y)))


class TestEmptyPoolClient:
    def test_empty_client_slot_is_inactive(self):
        """A scheduled client with zero samples must contribute nothing
        (b_t = 0), not silently train on dataset sample 0."""
        d = make_classification_data("mnist", 200, seed=0)
        parts = [np.arange(50), np.array([], np.int64), np.arange(50, 100)]
        test = make_classification_data("mnist", 50, seed=1)
        sim = SimConfig(batch_size=4, local_steps=1, eval_every=1000,
                        dropout_rate=0.0, seed=0)
        simul = DeviceFLSim(cnn.MNIST_CNN, d, parts, test, sim)
        (returned, q, _metrics), = simul.run_rounds(
            0, [[0, 1, 2]], [np.full(3, 1 / 3)])
        assert bool(returned[0]) and bool(returned[2])
        assert not bool(returned[1])          # empty client never returns
        assert q[1] == 0.0


class TestAsyncDispatch:
    """ISSUE-4: DeviceFLSim's dispatch_rounds/collect split must be
    bit-identical to blocking run_rounds, including with interleaved
    dispatches from another task's trainer in between (the overlapped
    ServiceScheduler pattern)."""

    def _sim(self, seed):
        d = make_classification_data("mnist", 400, seed=2)
        parts = partition_labels(d.labels, 6, "type1", 10, seed=2)
        test = make_classification_data("mnist", 120, seed=3)
        sim = SimConfig(batch_size=4, local_steps=1, eval_every=2,
                        dropout_rate=0.0, seed=seed)
        return DeviceFLSim(cnn.MNIST_CNN, d, parts, test, sim)

    def test_interleaved_dispatch_matches_blocking(self):
        subsets = [[0, 1], [2, 3], [4, 5], [0, 2]]
        weights = [np.full(2, 0.5) for _ in subsets]

        ref_a = self._sim(0)
        out_ref_a = ref_a.run_rounds(0, subsets, weights)
        ref_b = self._sim(7)
        out_ref_b = ref_b.run_rounds(0, subsets, weights)

        # overlapped: enqueue task A's chunk, then task B's, collect in
        # dispatch order — nothing may depend on when collect happens
        sim_a, sim_b = self._sim(0), self._sim(7)
        ha = sim_a.dispatch_rounds(0, subsets, weights)
        hb = sim_b.dispatch_rounds(0, subsets, weights)
        out_a = sim_a.collect(ha)
        out_b = sim_b.collect(hb)

        for got, ref in ((out_a, out_ref_a), (out_b, out_ref_b)):
            assert len(got) == len(ref)
            for (ra, qa, ma), (rb, qb, mb) in zip(got, ref):
                np.testing.assert_array_equal(ra, rb)
                np.testing.assert_array_equal(qa, qb)
                assert ma == mb               # includes eval accuracies
        assert sim_a.history == ref_a.history
        assert sim_b.history == ref_b.history

    def test_eval_rounds_enqueue_with_their_params(self):
        # eval accuracy must come from the params at the eval round even
        # though later dispatches (which donate the param buffers) are
        # enqueued before collect runs
        subsets = [[0, 1], [2, 3]]
        weights = [np.full(2, 0.5) for _ in subsets]
        sim = self._sim(0)
        h1 = sim.dispatch_rounds(0, subsets, weights)      # evals round 0
        h2 = sim.dispatch_rounds(2, subsets, weights)      # evals round 2
        out = sim.collect(h1) + sim.collect(h2)
        accs = {m["round"]: m["accuracy"] for _, _, m in out
                if "accuracy" in m}
        ref = self._sim(0)
        ref_out = ref.run_rounds(0, subsets, weights) + \
            ref.run_rounds(2, subsets, weights)
        ref_accs = {m["round"]: m["accuracy"] for _, _, m in ref_out
                    if "accuracy" in m}
        assert accs == ref_accs and set(accs) == {0, 2}


class TestEvalAlignment:
    def test_mid_chunk_eval_uses_that_rounds_params(self):
        """Chunked and per-round drivers must report identical accuracy
        for a mid-chunk eval round (the chunk splits at eval rounds)."""
        d = make_classification_data("mnist", 400, seed=2)
        parts = partition_labels(d.labels, 6, "type1", 10, seed=2)
        test = make_classification_data("mnist", 120, seed=3)
        sim = SimConfig(batch_size=4, local_steps=1, eval_every=2,
                        dropout_rate=0.0, seed=2)
        subsets = [[0, 1], [2, 3], [4, 5], [0, 2]]
        weights = [np.full(2, 0.5) for _ in subsets]

        chunked = DeviceFLSim(cnn.MNIST_CNN, d, parts, test, sim)
        chunked.run_rounds(0, subsets, weights)
        stepwise = DeviceFLSim(cnn.MNIST_CNN, d, parts, test, sim)
        for r in range(4):
            stepwise.run_rounds(r, [subsets[r]], [weights[r]])

        acc_a = {h["round"]: h["accuracy"] for h in chunked.history
                 if "accuracy" in h}
        acc_b = {h["round"]: h["accuracy"] for h in stepwise.history
                 if "accuracy" in h}
        assert set(acc_a) == set(acc_b) == {0, 2}
        for r in acc_a:
            assert acc_a[r] == pytest.approx(acc_b[r], abs=1e-6)
