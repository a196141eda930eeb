"""The benchmark's work counts and peaks table, on the CPU."""
import itertools

import pytest

from chipbench import counts, peaks


def _brute_force(lengths, H, D):
    """Count every multiply-add of q.k and p.v, key by key."""
    flops = 0
    for L, _h in itertools.product(lengths, range(H)):
        for i in range(L):
            for _j in range(i + 1):  # causal: keys 0..i
                flops += 2 * D + 2 * D
    return flops


@pytest.mark.parametrize("lengths", [[1], [3, 5], [7, 1, 4]])
def test_attention_flops_match_brute_force(lengths):
    H, D = 2, 3
    assert counts.causal_attention_flops(lengths, H, D) == \
        _brute_force(lengths, H, D)


def test_attention_flops_do_not_overflow_int32_lengths():
    import numpy as np

    L = np.full(6, 4096, np.int32)
    assert counts.causal_attention_flops(L, 8, 128) == \
        6 * 4 * 8 * 128 * (4096 * 4097 // 2)


def test_attention_bytes_count_live_tokens_once():
    # q and o: H heads each; k and v: Hkv heads each; D wide, 2 bytes
    assert counts.attention_bytes([3, 5], heads=4, kv_heads=1, head_dim=2,
                                  itemsize=2) == 8 * (2 * 4 + 2 * 1) * 2 * 2


def test_v5e_peaks():
    pk = peaks.peaks("TPU v5 lite")
    assert pk["bf16_flops"] == 197e12 and pk["hbm_bytes_per_s"] == 819e9


def test_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        peaks.peaks("cpu")
