"""Operation and byte counts against hand counts."""
import jax
import numpy as np

from bench import counts, harness
from bench.tests.small import ROOT

CIFAR = harness.read_json(ROOT / "bench" / "configs" / "cnn-cifar.json")["model"]


def test_cifar_cnn_parameters_by_hand():
    # conv1 3*3*3*32+32, conv2 3*3*32*64+64, fc1 (8*8*64)*256+256, fc2 256*10+10
    hand = (864 + 32) + (18432 + 64) + (4096 * 256 + 256) + (2560 + 10)
    assert hand == 1_070_794
    assert counts.cnn_params(CIFAR) == hand == CIFAR["params"]


def test_cifar_cnn_parameters_match_the_program():
    from repro.models import cnn
    shapes = jax.eval_shape(lambda: cnn.init_params(
        cnn.CIFAR_CNN, jax.random.PRNGKey(0)))
    assert sum(x.size for x in jax.tree_util.tree_leaves(shapes)) \
        == counts.cnn_params(CIFAR)


def test_cifar_cnn_forward_macs_by_hand():
    conv1 = 32 * 32 * 9 * 3 * 32          # 884,736
    conv2 = 16 * 16 * 9 * 32 * 64         # 4,718,592
    fc = 4096 * 256 + 256 * 10            # 1,051,136
    assert conv1 + conv2 + fc == 6_654_464
    assert counts.cnn_forward_macs(CIFAR) == 6_654_464


def test_train_flops_leave_out_the_images_gradient():
    macs = 6_654_464
    assert counts.cnn_train_flops_per_sample(CIFAR) \
        == 6 * macs - 2 * 884_736


def test_fedavg_agg_quality_bytes_at_cifar_width():
    k, p = 13, 1_070_794
    flops, nbytes = counts.fedavg_agg_quality_cost(k, p)
    # the (13, P) f32 stack read once, the (P,) aggregate written once,
    # 13 weights in, 13 dots + 13 squares + 1 aggregate square out
    assert nbytes == 13 * p * 4 + p * 4 + 13 * 4 + 27 * 4 == 59_964_624
    assert flops == 6 * k * p + 2 * p


def test_roofline_names_its_bound():
    peaks = {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = counts.roofline_s(*counts.fedavg_agg_quality_cost(
        13, 1_070_794), peaks)
    assert bound == "bytes"
    assert np.isclose(t, 59_964_624 / 819e9)
    t, bound = counts.roofline_s(1e15, 1.0, peaks)
    assert bound == "flops"


def test_segmented_topk_needs_one_read_of_the_keys():
    ops, nbytes = counts.segmented_topk_cost(8, 131_072, 4096)
    assert ops == 8 * 131_072
    assert nbytes == 8 * 131_072 * 4 + 8 * 4096 * 8
