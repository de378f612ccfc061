"""The served path's Pallas kernels compile for a TPU v5e.

Each test lowers one kernel at the width users reach (the paper's
CIFAR CNN, a 1M-client selection frontier, a 100k-item MKP) and
compiles it for a v5e that is described, not attached: the TPU
compiler refuses here what the chip would refuse (block tiling, VMEM),
without chip time. Every compile runs with ``jax_enable_x64`` off and
on (``tools/run.sh`` launches with it on). Nothing runs, so these
tests say nothing about results or speed.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and test workers import every file.
"""
import math
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import kernels, trace
from repro.kernels import compression, fedavg_agg, mkp_utility, segmented_topk
from repro.models import cnn


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    log_dir = os.environ.get("TPU_LOG_DIR")
    os.environ["TPU_LOG_DIR"] = "disabled"
    # a described-TPU executable can be written to the persistent cache
    # but not read back without a chip: keep these compiles out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        try:
            desc = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc
    finally:
        jax.config.update("jax_enable_compilation_cache", cache_on)
        compilation_cache.reset_cache()
        if log_dir is None:
            os.environ.pop("TPU_LOG_DIR", None)
        else:
            os.environ["TPU_LOG_DIR"] = log_dir


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cifar_p():
    shapes = jax.eval_shape(
        lambda: cnn.init_params(cnn.CIFAR_CNN, jax.random.PRNGKey(0)))
    return sum(math.prod(x.shape) for x in jax.tree_util.tree_leaves(shapes))


@pytest.fixture(params=[False, True], ids=["x32", "x64"])
def x64(request):
    with jax.enable_x64(request.param):
        yield request.param


def _compile_for_chip(fn, sharding, *shapes):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("K", [10, 20])
def test_fedavg_agg_quality(one_chip, cifar_p, x64, K):
    _compile_for_chip(fedavg_agg.fedavg_agg_quality, one_chip,
                      ((K, cifar_p), jnp.float32), ((K,), jnp.float32))


@pytest.mark.parametrize("k", [64, 2048, 4096, 8192])
def test_segmented_topk_fleet_frontier(one_chip, x64, k):
    # 8 shards of the default 131072-row shard: a 1M-client mirror
    text = _compile_for_chip(lambda r: segmented_topk.segmented_topk(r, k),
                             one_chip, ((8, 131072), jnp.float32))
    # what the benchmark's roofline reader needs (bench/kernels.py): every
    # custom call is a pass of the kernel, and the passes that write a
    # 3-D f32 first output write one frontier width
    calls = [trace.Event("", "", line.strip().removeprefix("ROOT "),
                         0.0, 0.0, {})
             for line in text.splitlines() if " custom-call(" in line]
    assert calls
    assert all(kernels.is_kernel(e, kernels.SEGMENTED_TOPK) for e in calls)
    widths = {s[2] for e in calls for s in kernels.shapes(e)[:1]
              if len(s) == 3}
    assert widths == {-(-k // 128) * 128}


def test_mkp_utility(one_chip, x64):
    n, m = 100_000, 3
    _compile_for_chip(mkp_utility.mkp_utility, one_chip,
                      ((n,), jnp.float32), ((n, m), jnp.float32),
                      ((m,), jnp.float32), ((n,), jnp.float32))


def test_topk_sparsify(one_chip, cifar_p, x64):
    k = math.ceil(0.001 * cifar_p)
    _compile_for_chip(lambda x: compression.topk_sparsify(x, k), one_chip,
                      ((10, cifar_p), jnp.float32))


@pytest.mark.parametrize("chunk", [256, 64])
def test_quantize_i8(one_chip, cifar_p, x64, chunk):
    _compile_for_chip(lambda x: compression.quantize_i8(x, chunk=chunk),
                      one_chip, ((10, cifar_p), jnp.float32))


def test_dequantize_i8(one_chip, cifar_p, x64):
    nc = -(-cifar_p // 256)
    _compile_for_chip(compression.dequantize_i8, one_chip,
                      ((10, cifar_p), jnp.int8), ((10, nc), jnp.float32))


def test_fedavg_agg_quality_i8(one_chip, cifar_p, x64):
    nc = -(-cifar_p // 256)
    _compile_for_chip(compression.fedavg_agg_quality_i8, one_chip,
                      ((10, cifar_p), jnp.int8), ((10, nc), jnp.float32),
                      ((10,), jnp.float32))
