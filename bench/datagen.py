"""Inputs of the benchmark, made from ``--seed``.

The generators are copies of the program's own (``ClientPoolState.random``
with ``criteria.random_histograms``, ``data.synthetic.make_classification_data``
and ``fl.partition.partition_labels``), kept here so that a change to the
program cannot move the benchmark's inputs. The image generator is
vectorised; its draws differ from the host original, its distribution
does not.
"""
from __future__ import annotations

import numpy as np

NUM_CRITERIA = 11     # Eq. (6) criteria: 7 resources, data size, data
DATA_SIZE, DATA_DIST = 7, 8    # distribution, model quality, behaviour


def sub_seed(seed: int, *tags: int | str) -> int:
    """A 31-bit seed for one consumer of ``seed``. ``seed`` may be any
    whole number, larger than 32 bits hold or negative."""
    words = [abs(int(seed)) % (1 << 64), abs(int(seed)) >> 64,
             int(seed < 0)]
    for t in tags:
        words.append(2 * abs(t) + (t < 0) if isinstance(t, int)
                     else int.from_bytes(t.encode(), "little") % (1 << 63))
    return int(np.random.SeedSequence(words).generate_state(1)[0] >> 1)


def rng(seed: int, *tags) -> np.random.Generator:
    return np.random.default_rng(sub_seed(seed, *tags))


# ---------------------------------------------------------------------------
# the fleet: registered clients and their criteria
# ---------------------------------------------------------------------------

def nid(hists: np.ndarray) -> np.ndarray:
    """Non-iid degree, Eq. (2): (max(h) - min(h)) / sum(h); 1 when empty."""
    h = np.asarray(hists, dtype=np.float64)
    total = h.sum(axis=-1)
    spread = h.max(axis=-1) - h.min(axis=-1)
    return np.where(total > 0, spread / np.maximum(total, 1e-12), 1.0)


def overall_score(scores: np.ndarray) -> np.ndarray:
    """Eq. (6) with unit weights: the sum of a client's criteria."""
    return np.asarray(scores, dtype=np.float64) @ np.ones(NUM_CRITERIA)


def random_histograms(n: int, classes: int, g: np.random.Generator,
                      lo: int = 10, hi: int = 200) -> np.ndarray:
    """Per client a uniform label count k ~ U{1..c}, k distinct labels,
    counts ~ U{lo..hi-1}."""
    perm = g.random((n, classes)).argsort(axis=1)
    k = g.integers(1, classes + 1, size=n)
    on = np.arange(classes) < k[:, None]
    vals = g.integers(lo, hi, size=(n, classes)).astype(np.float64)
    hists = np.zeros((n, classes))
    np.put_along_axis(hists, perm, np.where(on, vals, 0.0), axis=1)
    return hists


def fleet(n: int, classes: int, g: np.random.Generator,
          cost_a: float = 2.0, cost_b: float = 5.0):
    """``(scores (n, 11), histograms (n, classes), costs (n,))`` of a
    virtual fleet (paper §VIII-A): uniform resource criteria, data
    criteria from the histograms, integer linear costs, Eq. (7)."""
    scores = g.uniform(0.0, 1.0, size=(n, NUM_CRITERIA))
    hists = random_histograms(n, classes, g)
    sizes = hists.sum(axis=1)
    scores[:, DATA_SIZE] = sizes / max(sizes.max(), 1e-12)
    scores[:, DATA_DIST] = 1.0 - nid(hists)
    costs = np.rint(cost_a * overall_score(scores) + cost_b)
    return scores, hists, costs


# ---------------------------------------------------------------------------
# CIFAR-shaped images and their non-iid split
# ---------------------------------------------------------------------------

def cifar_images(labels: np.ndarray, g: np.random.Generator,
                 height: int = 32, width: int = 32, channels: int = 3,
                 classes: int = 10, noise: float = 0.55, shift: int = 4,
                 freq: int = 4) -> np.ndarray:
    """``(N, H, W, C)`` float32 images on the host: per class a smooth
    random prototype (a ``freq x freq`` field blown up), each sample its
    class prototype rolled by up to ``shift`` pixels each way, plus
    Gaussian noise, clipped to [0, 1]. Made on the host and staged as
    the program stages a dataset: an array made by a jitted call would
    take the compiler's output layout, which pads the 3-channel minor
    axis to 128 lanes on a TPU (26 GB for 50,000 images)."""
    base = g.normal(size=(classes, freq, freq, channels))
    up = np.repeat(np.repeat(base, height // freq + 1, axis=1),
                   width // freq + 1, axis=2)[:, :height, :width]
    protos = ((up - up.min()) / (np.ptp(up) + 1e-9)).astype(np.float32)
    n = len(labels)
    s = g.integers(-shift, shift + 1, size=(n, 2))
    rows = (np.arange(height)[None, :] - s[:, :1]) % height
    cols = (np.arange(width)[None, :] - s[:, 1:]) % width
    imgs = protos[np.asarray(labels)[:, None, None], rows[:, :, None],
                  cols[:, None, :]]
    imgs += np.float32(noise) * g.standard_normal(imgs.shape,
                                                  dtype=np.float32)
    np.clip(imgs, 0.0, 1.0, out=imgs)
    return imgs


def partition_type2(labels: np.ndarray, n_clients: int, classes: int,
                    g: np.random.Generator) -> list[np.ndarray]:
    """The paper's type-2 non-iid split: each client draws
    ``len(labels) // n_clients`` samples, 90% from one class and 10% from
    another, without replacement from per-class pools that recycle when
    exhausted."""
    by_class = [np.flatnonzero(labels == c) for c in range(classes)]
    for c in range(classes):
        g.shuffle(by_class[c])
    cursors = [0] * classes
    spc = len(labels) // n_clients

    def draw(c, k):
        pool = by_class[c]
        out = []
        while k > 0:
            take = min(k, len(pool) - cursors[c])
            if take <= 0:
                cursors[c] = 0
                g.shuffle(pool)
                continue
            out.append(pool[cursors[c]:cursors[c] + take])
            cursors[c] += take
            k -= take
        return np.concatenate(out)

    ratios = np.array([0.9, 0.1])
    parts = []
    for _ in range(n_clients):
        cls = g.choice(classes, size=len(ratios), replace=False)
        counts = np.maximum((ratios * spc).astype(int), 1)
        idx = np.concatenate([draw(c, k) for c, k in zip(cls, counts)])
        g.shuffle(idx)
        parts.append(idx)
    return parts


def client_criteria(parts: list[np.ndarray], labels: np.ndarray,
                    classes: int, g: np.random.Generator):
    """``(scores, histograms, costs)`` of a training pool: random
    resource criteria, data criteria from each client's partition."""
    n = len(parts)
    hists = np.stack([np.bincount(labels[p], minlength=classes)
                      for p in parts]).astype(np.float64)
    scores = g.uniform(0.3, 1.0, size=(n, NUM_CRITERIA))
    sizes = hists.sum(axis=1)
    scores[:, DATA_SIZE] = sizes / max(sizes.max(), 1)
    scores[:, DATA_DIST] = 1.0 - nid(hists)
    costs = np.rint(2.0 * overall_score(scores) + 5.0)
    return scores, hists, costs
