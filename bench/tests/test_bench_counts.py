"""Counts of operations and bytes (the dense family's ``Shapes``) against
hand-worked values, and the table of peaks."""
import os
import sys

import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from bench.families import phi3  # noqa: E402
from bench.harness import counts  # noqa: E402

PHI3 = {"hidden_size": 3072, "intermediate_size": 8192,
        "num_hidden_layers": 32, "num_attention_heads": 32,
        "num_key_value_heads": 32, "vocab_size": 32064}


def test_phi3_matmul_flops_per_token():
    # 32 x (4 x 3072^2 + 3 x 3072 x 8192) + 3072 x 32064 parameters
    assert phi3.Shapes.from_config(PHI3).matmul_flops_per_token == \
        7_444_758_528


def test_phi3_kv_bytes_per_token():
    # K and V, 32 layers, 32 heads of 96, bf16
    assert phi3.Shapes.from_config(PHI3).kv_bytes_per_token == 393_216


@pytest.mark.parametrize("swis,bits", [(None, 16.0),
                                       ({"group_size": 4, "n_shifts": 4},
                                        8.0),
                                       ({"group_size": 4, "n_shifts": 3},
                                        6.25)])
def test_weight_bits_follow_the_format(swis, bits):
    cfg = dict(PHI3, swis=swis) if swis else PHI3
    shapes = phi3.Shapes.from_config(cfg)
    assert shapes.weight_bits == bits
    layers = 3_623_878_656
    assert shapes.weight_bytes == pytest.approx(
        layers * bits / 8 + 3072 * 32064 * 2 + 65 * 3072 * 2)


def test_attention_counts_actual_lengths():
    s = phi3.Shapes.from_config(PHI3)
    # one decode row at position 9 sees 10 keys; a 3-token chunk at 4
    # sees 5 + 6 + 7
    per_key = 4 * 32 * 32 * 96
    assert s.attention_flops([(9, 1)]) == per_key * 10
    assert s.attention_flops([(4, 3)]) == per_key * 18
    assert s.kv_read_bytes([(9, 1), (4, 3)]) == (10 + 7) * 393_216
    assert s.kv_write_bytes([(9, 1), (4, 3)]) == 4 * 393_216


def test_explicit_head_dim_sets_the_attention_width():
    """A file's ``head_dim`` (128 here, not 3072 / 32 = 96) is the width
    of the Q, K and V heads: in the projections' parameters, the K/V
    bytes per token, the attention FLOPs and the kernel's query and
    output bytes."""
    wide = phi3.Shapes.from_config(dict(PHI3, head_dim=128))
    assert wide.head_dim == 128
    # K and V, 32 layers, 32 heads of 128, bf16
    assert wide.kv_bytes_per_token == 524_288
    assert wide.attention_flops([(9, 1)]) == 4 * 32 * 32 * 128 * 10
    # wq, wo (3072 x 4096) and wk, wv (3072 x 4096); the MLP as before
    per_layer = 4 * 3072 * 4096 + 3 * 3072 * 8192
    assert wide.layer_matmul_params == 32 * per_layer
    kernel = wide.attention_kernel([(4, 3)])
    assert kernel["flops"] == 4 * 32 * 32 * 128 * 18
    assert kernel["bytes"] == 7 * 524_288 + 3 * 32 * 32 * 128 * 6
    launch = wide.launch([(4, 3)])
    assert launch["bytes"] == (wide.weight_bytes + 7 * 524_288
                               + 3 * 524_288 + 3 * 3072 * 2)


def test_launch_counts_idle_rows_as_nothing():
    s = phi3.Shapes.from_config(PHI3)
    busy = s.launch([(9, 1), (0, 0)])
    alone = s.launch([(9, 1)])
    assert busy == alone
    assert alone["flops"] == (2 * 3_623_878_656 + 2 * 3072 * 32064
                              + s.attention_flops([(9, 1)]))


def test_least_seconds_names_its_bound():
    peak = counts.peaks("TPU v5 lite")
    assert counts.least_seconds(197e12, 1.0, peak) == (1.0, "compute")
    assert counts.least_seconds(1.0, 819e9, peak) == (1.0, "memory")


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        counts.peaks("TPU v9 imaginary")
