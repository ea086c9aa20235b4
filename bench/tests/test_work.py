"""The work counts against values worked out by hand."""
import pytest

from bench.lib.work import (Shapes, TickWork, attn_work, gemm_work,
                            least_time, tick_totals)

QWEN3_4B = dict(n_layers=36, d_model=2560, n_heads=32, n_kv_heads=8,
                head_dim=128, d_ff=9728, vocab=151936, act="swiglu")


def test_qwen3_4b_matmul_params_and_kv():
    s = Shapes(**QWEN3_4B)
    # per layer: q 2560x4096, k and v 2560x1024, o 4096x2560, 3 x 2560x9728
    per_layer = 2560 * 4096 + 2 * 2560 * 1024 + 4096 * 2560 + 3 * 2560 * 9728
    assert per_layer == 100_925_440
    assert s.matmul_params == 36 * per_layer          # 3.63e9
    assert s.kv_bytes_per_token == 36 * 2 * 8 * 128 * 2 == 147_456


def test_gemm_work_by_hand():
    # M=16 rows of K=2560 against N=4096 int8 codes, blocks of 32
    f, b = gemm_work(16, 2560, 4096, 8, 32)
    assert f == 2 * 16 * 2560 * 4096
    assert b == 2560 * 4096 + 80 * 4096 + 16 * 2560 * 2 + 16 * 4096 * 2
    f4, b4 = gemm_work(16, 2560, 4096, 4, 32)
    assert f4 == f and b4 == 2560 * 4096 / 2 + 80 * 4096 + 16 * 2560 * 2 \
        + 16 * 4096 * 2


def test_attn_work_by_hand():
    s = Shapes(**QWEN3_4B)
    # one decode query at position 99 sees 100 keys in 7 pages of 16
    f, b = attn_work(s, 99, 1, 16)
    assert f == 4 * 32 * 128 * 100
    assert b == 7 * 16 * 2 * 8 * 128 * 2 + 2 * 32 * 128 * 2
    # a chunk of 3 queries at cursor 16 sees 17 + 18 + 19 keys, 2 pages
    f, b = attn_work(s, 16, 3, 16)
    assert f == 4 * 32 * 128 * (17 + 18 + 19)
    assert b == 2 * 16 * 2 * 8 * 128 * 2 + 2 * 3 * 32 * 128 * 2


def test_least_time_says_which_bound():
    assert least_time(197e12, 1.0, 197e12, 819e9) == (1.0, "compute")
    assert least_time(1.0, 819e9, 197e12, 819e9) == (1.0, "memory")


def test_tick_totals():
    s = Shapes(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2, head_dim=16,
               d_ff=128, vocab=512, act="gelu")
    peak_f, peak_b = 1e12, 1e9
    ticks = [TickWork("decode", 4, [(10, 1, True), (20, 1, True)]),
             TickWork("chunk", 16, [(0, 12, True)])]
    out = tick_totals(s, ticks, 8, 8, peak_f, peak_b)
    assert out["positions"] == 20 and out["real_tokens"] == 14
    proj = [(64, 64), (64, 32), (64, 32), (64, 64), (64, 128), (128, 64)]
    gemm = sum(max(gemm_work(m, k, n, 8, 32)[0] / peak_f,
                   gemm_work(m, k, n, 8, 32)[1] / peak_b)
               for m in (4, 16) for k, n in proj) * 2
    assert out["gemm_least_s"] == pytest.approx(gemm)
    # attention least time: only the decode tick's kernel call
    fa = attn_work(s, 10, 1, 8)[0] + attn_work(s, 20, 1, 8)[0]
    ba = attn_work(s, 10, 1, 8)[1] + attn_work(s, 20, 1, 8)[1]
    assert out["attn_least_s"] == pytest.approx(
        2 * max(fa / peak_f, ba / peak_b))
    per_tok = 2 * s.matmul_params
    want = per_tok * 14 + 2 * sum(attn_work(s, o, q, 8)[0] for o, q in
                                  ((10, 1), (20, 1), (0, 12))) \
        + 3 * 2 * 64 * 512
    assert out["model_flops"] == pytest.approx(want)
