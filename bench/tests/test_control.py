"""The controls at a small size: the plain reference in the next
precision down, put in the program's place, must fail a cell's limits,
and the reference itself must pass them."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import datagen, harness
from bench.tests import small


def _kind(name):
    return harness.load_module(harness.BENCH / "kinds" / f"{name}.py", name)


@pytest.mark.parametrize("seed", [3, 2**35 + 9])
def test_cifar_bfloat16_control_fails(seed):
    kind = _kind("closed_tenants")
    config = small.small_config("cnn-cifar")
    traffic = small.small_traffic("tenants-4-closed")
    cell = kind.build(config, traffic, seed,
                      harness.Session(jax.devices()[:1], False))
    cell.build_tenants()
    cell._sweep_setup()
    first = cell.program_first_chunks()
    cell.scheduler = cell.sims = None
    limits = config["check"]["limits"]
    ref = cell.reference_first_chunks(first)
    sound = kind.compare(first, ref)
    assert all(sound[k] <= limits[k] for k in limits), sound
    ctl = kind.compare(cell.reference_first_chunks(
        first, dtype=jnp.bfloat16, precision=jax.lax.Precision.DEFAULT),
        ref)
    assert any(ctl[k] > limits[k] for k in limits), ctl


@pytest.mark.parametrize("seed", [4, 2**35 + 10])
def test_fleet_float32_control_fails(seed):
    config = small.small_config("fleet-1m")
    ref = harness.load_module(harness.BENCH / "configs" / "fleet-1m.py",
                              "fleet_ref")
    f = config["fleet"]
    scores, _, costs = datagen.fleet(f["clients"], f["classes"],
                                     datagen.rng(seed, "fleet"))
    th = np.full(f["thresholded_criteria"], f["threshold"])
    exact = ref.Greedy(scores, costs, th)
    low = ref.Greedy(scores, costs, th, dtype=np.float32)
    gap = 0.0
    for frac in (0.0025, 0.005, 0.01):
        b = round(frac * costs.sum(), 1)
        _, s, c = exact.select(b)
        _, ls, lc = low.select(b)
        gap = max(gap, abs(ls - s) / s, abs(lc - c) / c)
    assert gap > config["check"]["limits"]["total_gap"]
