"""Federated rounds in JAX — three scales (DESIGN.md §4):

- ``make_fl_round``: true FedAvg semantics at simulation scale — every
  scheduled client gets its own parameter copy (vmap over the client
  axis), runs E local SGD steps, and the server aggregates weighted
  deltas (Pallas ``fedavg_agg`` on TPU) and applies the server LR
  (paper §III: w_{t+1} = w_t − η Δ_t). One dispatch per round; batches
  arrive from the caller (host- or device-assembled).

- ``make_fl_rounds_scan``: the device-resident round data plane — S
  rounds per dispatch via ``lax.scan`` over precomputed schedule arrays
  (padded subsets/weights from stage 2), with on-device batch gather
  (fl.device_data), on-device dropout masks, the fused aggregation +
  quality kernel (kernels.fedavg_agg_quality: one pass over the stacked
  deltas yields Δ_t and every q_t cosine), and ``donate_argnums`` on
  the params so the server state never round-trips the host. A host
  checkpoint between chunks (core.lifecycle with round_chunk>1) handles
  stop_fn/eval/reputation. ``chunk_fn`` is also the unit of *overlap*
  in the multi-tenant service: a jit'd call returns unmaterialized
  device arrays immediately (JAX async dispatch), so
  ``DeviceFLSim.dispatch_rounds`` can enqueue one task's chunk while
  another task's still computes — never force a result (``np.asarray``
  / ``float`` / ``block_until_ready``) inside this module; callers
  decide when to block (``collect``).

- ``make_fedsgd_step``: datacenter-scale one-local-step equivalent —
  per-client weights fold into the loss so a single data-parallel
  backward implements the paper's weighted aggregation exactly; this is
  the ``train_step`` that the multi-pod dry-run lowers.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.fl import device_data
from repro.kernels import ops as kops
from repro.optim import apply_updates, sgd


def tree_sub(a, b):
    return jax.tree_util.tree_map(lambda x, y: x - y, a, b)


def tree_weighted_sum(trees_stacked, weights, use_kernel: bool = False):
    """Σ_k w_k · leaf[k] for every leaf with leading client axis K.

    Uses ``lax.dot_general`` with ``preferred_element_type=float32`` so
    accumulation happens in f32 *without* first materializing an f32
    copy of the stacked (K, P) tree (which doubled peak memory on bf16
    deltas); weights are cast to the leaf dtype instead. ``HIGHEST``
    precision keeps f32 products in f32 on a TPU, whose default
    multiplies them in bfloat16 (the CPU computes both alike).
    """
    if use_kernel:
        return kops.fedavg_agg_tree(trees_stacked, weights)

    def agg_leaf(leaf):
        K = leaf.shape[0]
        flat = leaf.reshape(K, -1)
        acc = jax.lax.dot_general(
            weights.astype(leaf.dtype), flat, (((0,), (0,)), ((), ())),
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32)
        return acc.reshape(leaf.shape[1:]).astype(leaf.dtype)

    return jax.tree_util.tree_map(agg_leaf, trees_stacked)


def flatten_stacked(trees_stacked):
    """Stacked pytree (leaves (K, ...)) -> ((K, P) array, unflatten).

    The fused aggregation+quality kernel wants one contiguous (K, P)
    matrix; ``unflatten`` restores a (P,) vector to the original tree
    structure/shapes/dtypes.
    """
    leaves, treedef = jax.tree_util.tree_flatten(trees_stacked)
    K = leaves[0].shape[0]
    ctype = jnp.result_type(*leaves)
    flats = [leaf.reshape(K, -1).astype(ctype) for leaf in leaves]
    sizes = [f.shape[1] for f in flats]
    splits = [int(s) for s in np.cumsum(sizes)[:-1]]
    shapes = [leaf.shape[1:] for leaf in leaves]
    dtypes = [leaf.dtype for leaf in leaves]

    def unflatten(vec):
        parts = jnp.split(vec, splits)
        out = [p.reshape(s).astype(d)
               for p, s, d in zip(parts, shapes, dtypes)]
        return jax.tree_util.tree_unflatten(treedef, out)

    return jnp.concatenate(flats, axis=1), unflatten


def _make_client_update(loss_fn: Callable, local_lr: float):
    """E local SGD steps for one client; returns (delta, mean_loss)."""
    opt = sgd(local_lr)

    def client_update(params, batches):
        state = opt.init(params)

        def step(carry, batch):
            p, s = carry
            (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, batch)
            upd, s = opt.update(grads, s, p)
            return (apply_updates(p, upd), s), loss

        (new_params, _), losses = jax.lax.scan(step, (params, state), batches)
        return tree_sub(params, new_params), losses.mean()

    return client_update


def _aggregate_and_quality(deltas, w, use_agg_kernel: bool,
                           fused_quality: bool):
    """Weighted aggregate Δ_t + per-client q_t = cos(Δ_t^(k), Δ_t).

    ``fused_quality`` routes through the single-pass aggregation +
    quality kernel (kernels.fedavg_agg_quality / its jnp oracle off-TPU);
    otherwise the legacy two-pass path: tree_weighted_sum then a vmapped
    cosine with the aggregate norm hoisted out of the K loop.
    """
    if fused_quality:
        flat, unflatten = flatten_stacked(deltas)
        agg_flat, dots, sq, asq = kops.fedavg_agg_quality(flat, w)
        q = dots / jnp.maximum(jnp.sqrt(sq) * jnp.sqrt(asq), 1e-12)
        return unflatten(agg_flat), q

    agg = tree_weighted_sum(deltas, w, use_agg_kernel)
    return agg, _quality_cosines(deltas, agg)


def _tree_dot(a, b):
    return sum(jnp.vdot(x.astype(jnp.float32), y.astype(jnp.float32),
                        precision=jax.lax.Precision.HIGHEST)
               for x, y in zip(jax.tree_util.tree_leaves(a),
                               jax.tree_util.tree_leaves(b)))


def _quality_cosines(deltas, agg):
    """Per-client q_t = cos(Δ_t^(k), Δ_t) against a given aggregate —
    the two-pass quality path, with the aggregate norm hoisted out of
    the K loop. Factored out so the sharded scan can reuse it with a
    psum'd (globally replicated) aggregate over local client shards."""
    nb = jnp.sqrt(_tree_dot(agg, agg))  # hoisted: identical for every k

    def cos_one(k):
        dk = jax.tree_util.tree_map(lambda leaf: leaf[k], deltas)
        num = _tree_dot(dk, agg)
        na = jnp.sqrt(_tree_dot(dk, dk))
        return num / jnp.maximum(na * nb, 1e-12)

    K = jax.tree_util.tree_leaves(deltas)[0].shape[0]
    return jax.vmap(cos_one)(jnp.arange(K))


def make_fl_round(loss_fn: Callable, local_lr: float = 0.05,
                  local_steps: int = 1, server_lr: float = 1.0,
                  use_agg_kernel: bool = False,
                  fused_quality: bool = False):
    """Build a jit'd FedAvg round.

    loss_fn(params, batch) -> (loss, metrics). Client batches arrive
    stacked: every leaf (K, local_steps, ...). Returns
    round_fn(params, client_batches, weights, mask) -> (params, info)
    where ``mask`` (K,) zeroes out dropped clients (behavior b_t = 0) and
    info carries per-client deltas' cosine-to-global q_t (paper §IV-C).
    ``fused_quality`` computes Δ_t and all q_t in one pass over the
    stacked deltas (the device data plane's default).
    """
    client_update = _make_client_update(loss_fn, local_lr)

    @jax.jit
    def round_fn(params, client_batches, weights, mask):
        deltas, losses = jax.vmap(client_update, in_axes=(None, 0))(
            params, client_batches)
        w = weights * mask
        w = w / jnp.maximum(w.sum(), 1e-9)
        agg, q = _aggregate_and_quality(deltas, w, use_agg_kernel,
                                        fused_quality)
        new_params = jax.tree_util.tree_map(
            lambda p, d: (p - server_lr * d).astype(p.dtype), params, agg)
        info = {"client_losses": losses, "q_values": q * mask,
                "mean_loss": jnp.sum(losses * w)}
        return new_params, info

    return round_fn


def make_fl_rounds_scan(loss_fn: Callable, local_lr: float = 0.05,
                        local_steps: int = 1, batch_size: int = 16,
                        server_lr: float = 1.0, dropout_rate: float = 0.0,
                        fused_quality: bool = True,
                        use_agg_kernel: bool = False,
                        compression=None, server_opt=None,
                        gather_fn: Callable | None = None):
    """Chunked multi-round driver: S rounds in ONE device dispatch.

    Returns ``chunk_fn(params, data, schedule, base_key)`` (jit'd, params
    donated) where

    - ``data`` is a :class:`repro.fl.device_data.DeviceDataset` (staged
      once; never re-transferred),
    - ``schedule`` is a dict of stacked per-round arrays from stage 2:
      ``rows (S, K)`` int32 positions into the dataset pools, ``weights
      (S, K)`` f32 FedAvg p_k, ``active (S, K)`` f32 padding mask
      (subsets sized n±δ are padded to a static K with actives first),
      ``round_ids (S,)`` int32 global round indices (PRNG folding —
      chunking-invariant randomness), plus — only under a lifecycle
      fault plan — ``arrival (S, K)`` f32 marking clients that reported
      by the round's collect close (late/dead clients are masked out of
      the aggregate on device; see docs/robustness.md),
    - ``base_key`` seeds batch sampling + dropout via per-(round, slot)
      key folds (fl.device_data.sample_positions).

    Each scan step gathers the round's client batches on device, draws
    the dropout mask on device, runs E local steps per client, and
    applies the fused aggregation+quality pass. Outputs stack across the
    chunk: ``(params', {"masks": (S,K), "q_values": (S,K),
    "client_losses": (S,K), "mean_loss": (S,)})``. The host only sees
    params/metrics at chunk boundaries (core.service round_chunk knob).

    Compressed update plane (docs/compression.md):

    - ``compression`` — a spec string / :class:`CompressionSpec`
      (``TaskRequest.compression``). When active, each round's stacked
      deltas are encoded per the spec, the server aggregates *from the
      compressed payloads* (fused int8 kernel, or densified top-k) and
      quality cosines are computed on the decoded updates; the per-round
      metrics gain a ``"bytes"`` column (arrived clients × per-client
      wire bytes). ``None``/"none" leaves the trace **bit-identical** to
      the uncompressed plane (asserted in tests/test_compression.py).
    - ``server_opt`` — a ``repro.optim`` Optimizer applied server-side
      to the pseudo-gradient Δ_t (FedAdam/FedYogi). The carry becomes
      ``(params, opt_state)``: ``chunk_fn((params, opt_state), ...)``
      returns ``((params', opt_state'), infos)``. ``server_lr`` is
      ignored in this mode (fold it into the optimizer's lr). ``None``
      keeps the plain SGD server step and the 1-ary carry.
    - ``gather_fn(data, rows, pos_u) -> batch tree`` — batch assembly
      hook; defaults to the image gather
      (:func:`repro.fl.device_data.gather_batches`). The LM plane passes
      :func:`repro.fl.device_data.gather_lm_batches`.
    """
    from repro.fl.compression import (CompressionSpec, aggregate_compressed,
                                      bytes_per_client)
    client_update = _make_client_update(loss_fn, local_lr)
    spec = CompressionSpec.parse(compression)
    gather = device_data.gather_batches if gather_fn is None else gather_fn

    @functools.partial(jax.jit, donate_argnums=(0,))
    def chunk_fn(carry, data, schedule, base_key):
        K = schedule["rows"].shape[1]
        # fault-mode schedules carry a per-round arrival mask (lifecycle
        # first-k collect, docs/robustness.md); its presence is a trace-
        # time pytree property, so the no-fault trace is unchanged
        has_arrival = "arrival" in schedule

        def one_round(carry, per_round):
            if server_opt is None:
                params, opt_state = carry, None
            else:
                params, opt_state = carry
            if has_arrival:
                rows, weights, active, rnd, arrival = per_round
            else:
                rows, weights, active, rnd = per_round
                arrival = None
            # a scheduled client with an empty pool cannot return an
            # update: treat its slot as inactive (b_t = 0, weight 0)
            # rather than silently training on the index-0 fallback.
            active = active * (jnp.take(data.sizes, rows, axis=0) > 0)
            mask_u, pos_u = device_data.sample_positions(
                base_key, rnd, K, local_steps, batch_size)
            mask = device_data.dropout_mask(mask_u, active, dropout_rate,
                                            arrival=arrival)
            batch = gather(data, rows, pos_u)
            deltas, losses = jax.vmap(client_update, in_axes=(None, 0))(
                params, batch)
            w = weights * mask
            w = w / jnp.maximum(w.sum(), 1e-9)
            if spec.active:
                flat, unflatten = flatten_stacked(deltas)
                agg_flat, dots, sq, asq = aggregate_compressed(flat, w, spec)
                q = dots / jnp.maximum(jnp.sqrt(sq) * jnp.sqrt(asq), 1e-12)
                agg = unflatten(agg_flat)
                per_client = bytes_per_client(spec, flat.shape[1],
                                              flat.dtype.itemsize)
            else:
                agg, q = _aggregate_and_quality(deltas, w, use_agg_kernel,
                                                fused_quality)
            if server_opt is None:
                params = jax.tree_util.tree_map(
                    lambda p, d: (p - server_lr * d).astype(p.dtype),
                    params, agg)
            else:
                # Δ_t is the server pseudo-gradient (FedOpt): the
                # adaptive optimizer's update replaces −server_lr·Δ_t
                upd, opt_state = server_opt.update(agg, opt_state, params)
                params = apply_updates(params, upd)
            info = {"masks": mask, "q_values": q * mask,
                    "client_losses": losses,
                    "mean_loss": jnp.sum(losses * w)}
            if spec.active:
                info["bytes"] = mask.sum() * jnp.float32(per_client)
            carry = params if server_opt is None else (params, opt_state)
            return carry, info

        xs = (schedule["rows"], schedule["weights"], schedule["active"],
              schedule["round_ids"])
        if has_arrival:
            xs = xs + (schedule["arrival"],)
        return jax.lax.scan(one_round, carry, xs)

    return chunk_fn


def make_fl_rounds_scan_sharded(loss_fn: Callable, local_lr: float = 0.05,
                                local_steps: int = 1, batch_size: int = 16,
                                server_lr: float = 1.0,
                                gather_fn: Callable | None = None,
                                mesh=None):
    """Client-sharded variant of :func:`make_fl_rounds_scan` for large
    models: the round's client axis K is split over the mesh's data
    axes with ``shard_map``, each shard runs its K/n clients' local
    updates, and the weighted aggregate Δ_t (plus the weight and loss
    normalizers) is ``psum``'d across shards — the HomebrewNLP-style
    psum aggregation the ROADMAP names, finally wiring
    ``launch/mesh.py`` + ``sharding/specs.py`` into the FL path.

    Same ``chunk_fn(params, data, schedule, base_key)`` contract and
    the same slot-keyed randomness as the unsharded scan (each shard
    draws its *global* slots via ``sample_positions(slot_offset=...)``),
    so per-client batches, masks and deltas are identical; only the
    f32 reduction order of the aggregate differs (allclose, not
    bit-equal — asserted in tests/test_placement.py). K must divide by
    the data-axis size (pad subsets up — ``DeviceFLSim`` rounds its
    static K up when handed a mesh).

    ``mesh=None`` builds :func:`repro.launch.mesh.make_host_mesh` (all
    local devices on "data"; force N CPU devices with
    ``REPRO_HOST_DEVICES=N tools/run.sh ...``). Scope: the uncompressed
    plain-SGD-server plane only — ``compression`` / ``server_opt`` stay
    on the unsharded scan, and client dropout is not simulated here
    (its all-dropped fallback election is global across K; a per-shard
    election would diverge). Fault-mode ``arrival`` masks are
    supported — they shard with the schedule.
    """
    from jax.sharding import PartitionSpec as P

    from repro.launch.mesh import make_host_mesh
    from repro.sharding import specs as sharding_specs

    if mesh is None:
        mesh = make_host_mesh()
    dax = sharding_specs.data_axes(mesh)
    axis = dax if len(dax) > 1 else dax[0]
    n_shard = sharding_specs.mesh_axis_size(mesh, dax)
    client_update = _make_client_update(loss_fn, local_lr)
    gather = device_data.gather_batches if gather_fn is None else gather_fn

    @functools.partial(jax.jit, donate_argnums=(0,))
    def chunk_fn(params, data, schedule, base_key):
        K = schedule["rows"].shape[1]
        if K % n_shard:
            raise ValueError(
                f"client axis K={K} must be divisible by the data-axis "
                f"size {n_shard}; pad subsets (pad_subset_to) up")
        K_local = K // n_shard
        has_arrival = "arrival" in schedule

        def body(params, data, schedule, base_key):
            shard = jnp.int32(0)
            for a in dax:
                shard = shard * sharding_specs.mesh_axis_size(mesh, a) \
                    + jax.lax.axis_index(a)
            offset = shard * K_local

            def one_round(params, per_round):
                if has_arrival:
                    rows, weights, active, rnd, arrival = per_round
                else:
                    rows, weights, active, rnd = per_round
                    arrival = None
                active = active * (jnp.take(data.sizes, rows, axis=0) > 0)
                mask_u, pos_u = device_data.sample_positions(
                    base_key, rnd, K_local, local_steps, batch_size,
                    slot_offset=offset)
                mask = device_data.dropout_mask(mask_u, active, 0.0,
                                                arrival=arrival)
                batch = gather(data, rows, pos_u)
                deltas, losses = jax.vmap(client_update, in_axes=(None, 0))(
                    params, batch)
                w = weights * mask
                wsum = jax.lax.psum(w.sum(), axis)
                w = w / jnp.maximum(wsum, 1e-9)
                agg = jax.lax.psum(tree_weighted_sum(deltas, w), axis)
                q = _quality_cosines(deltas, agg)
                params = jax.tree_util.tree_map(
                    lambda p, d: (p - server_lr * d).astype(p.dtype),
                    params, agg)
                info = {"masks": mask, "q_values": q * mask,
                        "client_losses": losses,
                        "mean_loss": jax.lax.psum(jnp.sum(losses * w),
                                                  axis)}
                return params, info

            xs = (schedule["rows"], schedule["weights"],
                  schedule["active"], schedule["round_ids"])
            if has_arrival:
                xs = xs + (schedule["arrival"],)
            return jax.lax.scan(one_round, params, xs)

        sched_spec = {k: P(None, axis) for k in schedule}
        sched_spec["round_ids"] = P()
        shard_spec = {"masks": P(None, axis), "q_values": P(None, axis),
                      "client_losses": P(None, axis), "mean_loss": P()}
        mapped = jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(), P(), sched_spec, P()),
            out_specs=(P(), shard_spec),
            check_vma=False)
        return mapped(params, data, schedule, base_key)

    return chunk_fn


def make_fedsgd_step(loss_fn: Callable, optimizer, microbatches: int = 1,
                     unroll_microbatches: bool = False):
    """Datacenter-scale train_step (the dry-run target).

    batch carries per-example ``weights`` = p_{k(example)} / examples_of_k,
    so the weighted CE gradient equals the paper's Δ_t = Σ_k p_k Δ_t^(k)
    for one local step. Sharding in/out specs come from sharding/specs.py.

    ``microbatches > 1`` (§Perf): gradient accumulation — the global batch
    splits along dim0 into M microbatches scanned sequentially; live
    activation memory shrinks ~M× at the cost of f32 grad-accumulator
    state. Weighted-loss semantics are preserved by accumulating
    (Σ w·loss, Σ w)-weighted grads. ``unroll_microbatches`` uses a Python
    loop instead of lax.scan (dry-run cost fidelity).
    """

    def grads_of(params, batch):
        (loss, metrics), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            params, batch)
        return loss, metrics, grads

    def train_step(params, opt_state, batch):
        if microbatches <= 1:
            loss, metrics, grads = grads_of(params, batch)
        else:
            split = jax.tree_util.tree_map(
                lambda x: x.reshape(microbatches, x.shape[0] // microbatches,
                                    *x.shape[1:]), batch)
            w_tot = jnp.maximum(batch.get(
                "weights", jnp.ones(())).sum(), 1e-9)

            def one(mb):
                loss, metrics, grads = grads_of(params, mb)
                # per-microbatch loss is weight-normalized inside loss_fn;
                # re-scale so the accumulated grad matches the full batch.
                scale = (mb["weights"].sum() / w_tot) if "weights" in mb \
                    else 1.0 / microbatches
                grads = jax.tree_util.tree_map(
                    lambda g: g.astype(jnp.float32) * scale, grads)
                return loss * scale, grads

            if unroll_microbatches:
                loss = 0.0
                grads = None
                for i in range(microbatches):
                    mb = jax.tree_util.tree_map(lambda x: x[i], split)
                    l, g = one(mb)
                    loss = loss + l
                    grads = g if grads is None else jax.tree_util.tree_map(
                        jnp.add, grads, g)
            else:
                def body(acc, mb):
                    l, g = one(mb)
                    return (acc[0] + l,
                            jax.tree_util.tree_map(jnp.add, acc[1], g)), None
                zero = (jnp.zeros((), jnp.float32),
                        jax.tree_util.tree_map(
                            lambda p: jnp.zeros(p.shape, jnp.float32), params))
                (loss, grads), _ = jax.lax.scan(body, zero, split)
            metrics = {"loss": loss}
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = apply_updates(params, updates)
        return params, opt_state, metrics

    return train_step
