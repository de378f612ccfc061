"""Plain reference of the ``cnn-cifar`` configuration: the paper's CIFAR
CNN trained by FedAvg, one round at a time, in straightforward
``jax.numpy`` at ``Precision.HIGHEST`` (float32 products kept in
float32 on a TPU). Imports nothing of the program under test.

It follows the served path's definition of a round:

- batch positions and the dropout draw come from per-(round, slot) keys,
  ``fold_in(fold_in(key(seed), round), slot)`` split into a dropout
  uniform and ``(local_steps, batch)`` position uniforms; a position is
  ``floor(u * size)`` into the client's own sample list;
- a client drops when its uniform is below the dropout rate; if every
  client would drop, the first one is kept;
- each client runs ``local_steps`` SGD steps of mean cross-entropy, and
  its loss is the mean over those steps;
- the server weights clients by data size, zeroes the dropped ones and
  renormalises, applies ``params - server_lr * sum_k w_k delta_k``, and
  reports ``q_k = cos(delta_k, aggregate)`` for returned clients and the
  weighted mean loss.

``dtype`` and ``precision`` select the arithmetic; the benchmark's
control runs this same reference in bfloat16 at default precision.
``fault`` plants one of the faults a timed path can have: ``"half"``
trains on the first half of every batch, ``"flip"`` negates the first
client's update where it is produced.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


def init_params(m: dict, seed32: int, dtype=jnp.float32):
    """He-normal convs, 1/sqrt(fan-in) dense layers, zero biases, from
    ``key(seed)`` split four ways in layer order."""
    k1, k2, k3, k4 = jax.random.split(jax.random.PRNGKey(seed32), 4)
    c, c1, c2 = m["channels"], m["conv1"], m["conv2"]
    hid, ncls = m["hidden"], m["num_classes"]
    flat = (m["height"] // 4) * (m["width"] // 4) * c2

    def conv(k, shape):
        fan_in = shape[0] * shape[1] * shape[2]
        return jax.random.normal(k, shape) * (2.0 / fan_in) ** 0.5

    p = {"conv1": {"w": conv(k1, (3, 3, c, c1)), "b": jnp.zeros(c1)},
         "conv2": {"w": conv(k2, (3, 3, c1, c2)), "b": jnp.zeros(c2)},
         "fc1": {"w": jax.random.normal(k3, (flat, hid)) * flat ** -0.5,
                 "b": jnp.zeros(hid)},
         "fc2": {"w": jax.random.normal(k4, (hid, ncls)) * hid ** -0.5,
                 "b": jnp.zeros(ncls)}}
    return jax.tree_util.tree_map(lambda x: x.astype(dtype), p)


def forward(params, images, precision):
    def block(x, p):
        y = jax.lax.conv_general_dilated(
            x, p["w"], (1, 1), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"), precision=precision)
        y = jax.nn.relu(y + p["b"])
        return jax.lax.reduce_window(y, -jnp.inf, jax.lax.max,
                                     (1, 2, 2, 1), (1, 2, 2, 1), "VALID")

    x = block(images.astype(params["conv1"]["w"].dtype), params["conv1"])
    x = block(x, params["conv2"])
    x = x.reshape(x.shape[0], -1)
    x = jax.nn.relu(jnp.dot(x, params["fc1"]["w"], precision=precision)
                    + params["fc1"]["b"])
    return jnp.dot(x, params["fc2"]["w"], precision=precision) \
        + params["fc2"]["b"]


def loss(params, images, labels, precision):
    logp = jax.nn.log_softmax(forward(params, images, precision)
                              .astype(jnp.float32))
    return -jnp.take_along_axis(logp, labels[:, None], axis=1)[:, 0].mean()


def draws(seed32: int, rnd: int, slots: int, steps: int, batch: int):
    """``(dropout uniforms (slots,), position uniforms (slots, steps,
    batch))`` of one round."""
    base = jax.random.PRNGKey(seed32)

    def one(slot):
        ku, kb = jax.random.split(
            jax.random.fold_in(jax.random.fold_in(base, rnd), slot))
        return (jax.random.uniform(ku, ()),
                jax.random.uniform(kb, (steps, batch)))
    return jax.vmap(one)(jnp.arange(slots))


@functools.partial(jax.jit, static_argnames=("lr", "server_lr", "steps",
                                             "precision", "fault"))
def _round(params, images, labels, weights, keep, *, lr, server_lr, steps,
           precision, fault):
    """One round over ``K`` client slots: images ``(K, steps, b, H, W,
    C)``, labels ``(K, steps, b)``, weights and keep ``(K,)``."""
    if fault == "half":
        half = images.shape[2] // 2
        images, labels = images[:, :, :half], labels[:, :, :half]

    def client(imgs, labs):
        p, losses = params, []
        for e in range(steps):
            v, g = jax.value_and_grad(loss)(p, imgs[e], labs[e], precision)
            p = jax.tree_util.tree_map(lambda a, b: a - lr * b, p, g)
            losses.append(v)
        delta = jax.tree_util.tree_map(lambda a, b: a - b, params, p)
        return delta, jnp.mean(jnp.stack(losses))

    deltas, losses = jax.vmap(client)(images, labels)
    if fault == "flip":
        deltas = jax.tree_util.tree_map(lambda d: d.at[0].multiply(-1),
                                        deltas)
    w = weights * keep
    w = w / jnp.maximum(w.sum(), 1e-9)
    agg = jax.tree_util.tree_map(
        lambda d: jnp.tensordot(w.astype(d.dtype), d, axes=1,
                                precision=precision), deltas)
    flat_d = jnp.concatenate(
        [d.reshape(d.shape[0], -1).astype(jnp.float32)
         for d in jax.tree_util.tree_leaves(deltas)], axis=1)
    flat_a = jnp.concatenate([a.reshape(-1).astype(jnp.float32)
                              for a in jax.tree_util.tree_leaves(agg)])
    dots = jnp.dot(flat_d, flat_a, precision=HIGHEST)
    norms = jnp.sqrt(jnp.sum(flat_d * flat_d, axis=1)) \
        * jnp.sqrt(jnp.sum(flat_a * flat_a))
    q = dots / jnp.maximum(norms, 1e-12) * keep
    new = jax.tree_util.tree_map(lambda p, a: p - server_lr * a, params, agg)
    return new, q, jnp.sum(losses * w)


def replay(m: dict, t: dict, seed32: int, images, labels: np.ndarray,
           parts: list[np.ndarray], rounds: list[tuple[int, list[int]]],
           slots: int, *, dtype=jnp.float32, precision=HIGHEST,
           fault: str | None = None):
    """Run ``rounds`` (``(round index, client ids)``, consecutive, from
    the initial weights) and return ``(params after them, [(keep (k,),
    q (k,), loss) per round])``, every value on the host in float32.
    Client slots beyond a round's clients are padding, weight 0."""
    params = init_params(m, seed32, dtype)
    steps, batch = t["local_steps"], t["batch_size"]
    out = []
    for rnd, subset in rounds:
        k = len(subset)
        mask_u, pos_u = (np.asarray(a) for a in
                         draws(seed32, rnd, slots, steps, batch))
        keep = mask_u[:k] >= np.float32(t["dropout_rate"])
        if not keep.any():
            keep[0] = True
        sizes = np.array([len(parts[c]) for c in subset], dtype=np.float64)
        idx = np.empty((slots, steps, batch), dtype=np.int64)
        for i in range(slots):
            own = parts[subset[min(i, k - 1)]]
            sz = np.float32(len(own))
            pos = np.floor(pos_u[i] * sz).astype(np.int64)
            idx[i] = own[np.clip(pos, 0, len(own) - 1)]
        w = np.zeros(slots, np.float32)
        w[:k] = (sizes / max(sizes.sum(), 1e-12)).astype(np.float32)
        kp = np.zeros(slots, np.float32)
        kp[:k] = keep
        flat = jnp.asarray(idx.reshape(-1))
        imgs = jnp.take(images, flat, axis=0).reshape(
            slots, steps, batch, *images.shape[1:])
        labs = jnp.asarray(labels[idx].astype(np.int32))
        params, q, lv = _round(params, imgs, labs, jnp.asarray(w),
                               jnp.asarray(kp), lr=t["local_lr"],
                               server_lr=t["server_lr"], steps=steps,
                               precision=precision, fault=fault)
        out.append((keep, np.asarray(q, np.float32)[:k], float(lv)))
    host = jax.tree_util.tree_map(lambda x: np.asarray(x, np.float32),
                                  params)
    return host, out
