"""The benchmark's FLOP and byte counts against hand counts."""
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import flops  # noqa: E402


@pytest.mark.parametrize("features,depth,want", [
    # 2*4H(In+H) summed over layers (64->32, 32->16, 16->8, 8->16,
    # 16->32, 32->64) and (32->16, 16->32)
    (64, 6, 96_768),
    (32, 2, 18_432),
])
def test_flops_per_row_timestep(features, depth, want):
    assert flops.flops_per_row_timestep(features, depth) == want


def test_layer_shapes_match_the_paper():
    assert flops.layer_shapes(64, 6) == [(64, 32), (32, 16), (16, 8), (8, 16),
                                        (16, 32), (32, 64)]
    assert flops.layer_shapes(32, 2) == [(32, 16), (16, 32)]


def test_bytes_per_row_timestep():
    assert flops.bytes_per_row_timestep(64, 6, "score") == 256
    # sample in, (h, c) of hidden 32+16+8+16+32+64 = 168 read and written,
    # the error sum updated
    assert flops.bytes_per_row_timestep(64, 6, "step") == 256 + 16 * 168 + 12
    assert flops.bytes_per_row_timestep(32, 2, "step") == 128 + 16 * 48 + 12


def test_useful_work_counts_requests_and_rows_only():
    fl, nb = flops.useful_work(64, 6, "score", row_timesteps=1000, requests=10)
    assert fl == 1000 * 96_768 and nb == 1000 * 256 + 40
    fl, nb = flops.useful_work(32, 2, "step", row_timesteps=7, requests=7)
    assert fl == 7 * 18_432 and nb == 7 * (128 + 768 + 12)
