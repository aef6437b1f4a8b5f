"""Work counts and the peaks table."""
import pytest

from chipbench import work


def test_dense_epoch_reads_every_entry_once():
    flops, nbytes = work.epoch_work("dense", 16384, 8192, 0, 8)
    assert nbytes == 16384 * 8192 * 4 == 536870912
    assert flops == 2 * 16384 * 8192 * 8


@pytest.mark.parametrize("path", ["matfree", "matfree_sharded"])
def test_ell_epoch_reads_value_and_index_per_entry(path):
    flops, nbytes = work.epoch_work(path, 8192, 8192, 100663, 8)
    assert nbytes == 8 * 100663
    assert flops == 4 * 100663 * 8


def test_v5e_roofline_is_bandwidth_bound_for_the_dense_epoch():
    peak = work.peaks("TPU v5 lite")
    assert peak["flops_per_s"] == 197e12 and peak["bytes_per_s"] == 819e9
    t, bound = work.least_seconds(*work.epoch_work("dense", 16384, 8192, 0, 8),
                                  peak)
    assert bound == "bytes" and t == pytest.approx(536870912 / 819e9)


def test_unknown_device_or_path_is_an_error():
    with pytest.raises(KeyError):
        work.peaks("TPU v9 imaginary")
    with pytest.raises(ValueError):
        work.epoch_work("sparse_magic", 1, 1, 1, 1)
