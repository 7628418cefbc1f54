"""The table of peaks and the group-by kernel's least bytes."""
import pytest

from bench import peaks


def test_v5e_peaks_and_an_unknown_kind():
    v5e = peaks.peaks("TPU v5 lite")
    assert v5e["hbm_bytes_per_s"] == 819e9
    assert v5e["bf16_flops_per_s"] == 197e12 and v5e["hbm_bytes"] == 16e9
    with pytest.raises(KeyError):
        peaks.peaks("TPU v9 imaginary")


@pytest.mark.parametrize("rows, values, groups, words, want", [
    (1000, 1, 7, 1, 4 * 1000 * 2 + 4 * 7),
    (19_999_013, 1, 4, 1, 4 * 19_999_013 * 2 + 4 * 4),
    (5_000_000, 2, 12, 1, 4 * 5_000_000 * 3 + 4 * 24),
    (10_000_001, 1, 7, 2, 4 * 10_000_001 * 2 + 4 * 7 * 2),
])
def test_groupby_sum_bytes_count_the_algorithm(rows, values, groups, words,
                                               want):
    got = peaks.groupby_sum_bytes(rows, values, groups, words)
    assert got == want
    # the kernel reads codes and values padded to (rows, 128) tiles of
    # whole blocks and writes (values, groups padded to 8, 128) partials:
    # none of that counts
    tiles = -(-rows // (64 * 1024)) * 64 * 1024
    padded = 4 * tiles * (1 + values) + 4 * values * 8 * 128 * words
    assert got < padded
