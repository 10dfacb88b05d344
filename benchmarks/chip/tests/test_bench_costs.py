"""Operations and bytes from shapes, against hand counts."""
import pytest

import bench_tiny  # noqa: F401  (puts the benchmark on the path)
from chipbench import costs


def test_gemm_cost_by_hand():
    # M=8 rows, N=256 out, K=512 in, group 256, LoRC rank 8
    f, b = costs.gemm_cost(8, 256, 512, 256, 8)
    assert f == 2 * 8 * 256 * 512 + 2 * 8 * 8 * (256 + 512)
    codes = 256 * 512 // 2           # two FP4 codes a byte
    shifts = 256 * 2                 # one int8 per row and group
    s_max = 256 * 4                  # one f32 per row
    lorc = 2 * 8 * (256 + 512)       # bf16 A (256, 8) and B (8, 512)
    acts = 2 * 8 * 512 + 2 * 8 * 256  # bf16 in, bf16 out
    assert b == codes + shifts + s_max + lorc + acts


ARCH = {"d_model": 64, "n_heads": 4, "n_kv_heads": 2, "d_ff": 128,
        "n_layers": 3, "vocab_size": 100, "mlp_gated": True}
QUANT = {"group_size": 64, "lorc_rank": 8}


def test_linears_of_a_gated_gqa_layer():
    hd = 16
    assert costs.linears(ARCH) == [(64, 64), (32, 64), (32, 64), (64, 64),
                                   (128, 64), (64, 128), (128, 64)]
    assert costs.linears(dict(ARCH, mlp_gated=False))[-1] == (64, 128)
    assert hd * 4 == 64


def test_decode_attn_bytes_by_hand():
    # two rows at lengths 15 and 16 (pages of 16): 1 and 2 pages after the
    # appended token; one idle slot (1 page)
    st = costs.Step(slots=3, decode_lens=(15, 16))
    f, b = costs.step_decode_attn(st, ARCH, page=16)
    page_bytes = 2 * (16 * 2 * 16 + 4 + 4 * 2)  # K and V codes + scales
    qo = 3 * 4 * 16 * 2 * 2                      # bf16 q and out per slot
    assert b == 3 * ((1 + 2 + 1) * page_bytes + qo)
    assert f == 3 * 4 * 4 * 16 * (16 + 17 + 1)


def test_gemm_least_time_counts_every_row_of_each_call():
    st = costs.Step(slots=4, decode_lens=(3,), chunk_pos=0, chunk_take=5,
                    chunk_padded=8)
    peaks = {"flops": 1e6, "hbm_bytes_per_s": 1e5}
    m = 4 + 8
    want = 0.0
    for n, k in costs.linears(ARCH):
        f, b = costs.gemm_cost(m, n, k, 64, 8)
        want += max(f / 1e6, b / 1e5)
    got = costs.step_gemm_least_s(st, ARCH, QUANT, peaks)
    assert got == pytest.approx(3 * want)


def test_model_flops_count_true_tokens_only():
    st = costs.Step(slots=4, decode_lens=(3,), chunk_pos=32, chunk_take=5,
                    chunk_padded=8)
    per_tok = sum(2 * n * k + 2 * 8 * (n + k) for n, k in costs.linears(ARCH))
    attn = 4 * 64 * 4 + 4 * 64 * sum(32 + j + 1 for j in range(5))
    head = 2 * 64 * 100 * 2
    assert costs.model_flops(st, ARCH, QUANT) == 3 * (per_tok * 6 + attn) + head


@pytest.mark.parametrize("n,want", [(0, 1), (1, 1), (2, 2), (3, 4), (256, 256),
                                    (257, 512)])
def test_next_pow2(n, want):
    assert costs.next_pow2(n) == want
