"""flops.py against counts worked by hand from the published shapes."""

import os

import pytest

from benchmark import cells, flops


def _config(name):
    return cells.load_json(os.path.join(cells.HERE, "configs", name + ".json"))


# Worked by hand, per block: wq + wk + wv + wo, then gate + up + down.
# mistral:   4096*4096 + 2*4096*1024 + 4096*4096 = 41,943,040
#            3*4096*14336                        = 176,160,768
# internlm2: 2048*2048 + 2*2048*1024 + 2048*2048 = 12,582,912
#            3*2048*8192                         = 50,331,648
HAND = {
    "mistral-7b-l1": dict(
        seq=4096,
        layer=41_943_040 + 176_160_768,
        head=4096 * 32000,
        norms=3 * 4096,
        # causal attention: 6 * seq * (heads * head_dim) per layer
        attention=6 * 4096 * 4096 * 1,
    ),
    "internlm2-1.8b-l3": dict(
        seq=8192,
        layer=12_582_912 + 50_331_648,
        head=2048 * 92544,
        norms=7 * 2048,
        attention=6 * 8192 * 2048 * 3,
    ),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_counts_match_hand_worked(name):
    c, h = _config(name), HAND[name]
    layers = c["num_hidden_layers"]
    assert flops.layer_matmul_params(c) == h["layer"]
    assert flops.matmul_params(c) == layers * h["layer"] + h["head"]
    # the embedding table is trained, and crosses the replica axis, but
    # multiplies nothing
    assert flops.total_params(c) == layers * h["layer"] + 2 * h["head"] + h["norms"]
    assert flops.attention_flops_per_token(c, h["seq"]) == h["attention"]
    assert flops.model_flops_per_token(c, h["seq"]) == (
        6 * (layers * h["layer"] + h["head"]) + h["attention"]
    )


def test_the_two_totals():
    assert flops.model_flops_per_token(_config("mistral-7b-l1"), 4096) == 2_195_718_144
    assert flops.model_flops_per_token(_config("internlm2-1.8b-l3"), 8192) == 2_571_632_640
    assert flops.total_params(_config("mistral-7b-l1")) == 480_260_096
    assert flops.total_params(_config("internlm2-1.8b-l3")) == 567_818_240


def test_kernel_work_from_shapes():
    c = _config("mistral-7b-l1")
    # all tokens' attention term, and it is compute-bound on a v5e
    assert flops.flash_flops_per_step(c, 4, 4096) == 6 * 4096 * 4096 * 4 * 4096
    assert (flops.flash_flops_per_step(c, 4, 4096) / 197e12
            > flops.flash_bytes_per_step(c, 4, 4096) / 819e9)
    # int8: 4 B in, 1 B + 4/512 B out per value, and the same back
    n = 480_260_096
    assert flops.quant_bytes_per_step(c, 8) == 2 * (4 * n + n + 4 * n / 512)
