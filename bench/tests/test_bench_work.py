"""Work counts of the φ update are of the unpadded shapes Eq. 10 needs, and
the peaks table refuses a device it does not know."""
from __future__ import annotations

import pytest

from bench import work

PEAK = work.peaks("TPU v5 lite")


def test_dense_counts_the_unpadded_links():
    # 50 runs of N = 30: 30 x 30 delays each (the kernel pads to 128 x 128)
    w = work.phi_update(50, 30)
    assert w["bytes"] == 4 * 50 * 30 * 30 + 3 * 4 * 50 * 30
    assert w["flops"] == 3 * 50 * 30 * 30 + 4 * 50 * 30


def test_sparse_counts_delays_ids_and_gathered_inverse():
    w = work.phi_update(2, 4096, k=16)
    links = 2 * 4096 * 16
    assert w["bytes"] == links * (4 + 4 + 4) + 3 * 4 * 2 * 4096
    assert w["flops"] == 3 * links + 4 * 2 * 4096


@pytest.mark.parametrize("n", [30, 100, 128, 129])
def test_work_grows_with_n_not_with_tiles(n):
    # padding to a 128 multiple would make 100 and 128 equal; work must not
    assert work.phi_update(1, n)["bytes"] < work.phi_update(1, n + 1)["bytes"]


def test_least_seconds_is_the_larger_bound():
    w = work.phi_update(50, 30)
    t = work.least_seconds(w, PEAK)
    assert t == max(w["bytes"] / 819e9, w["flops"] / 197e12)
    assert t == w["bytes"] / 819e9          # memory-bound


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError, match="no peaks"):
        work.peaks("TPU v99")
