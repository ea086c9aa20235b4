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


TICKS = [TickWork("decode", 16, [(99, 1, True), (1500, 1, True),
                                 (37, 1, True)]),
         TickWork("mixed", 1024, [(64, 64, False), (200, 1, True),
                                  (900, 1, True)]),
         TickWork("chunk", 32, [(0, 20, True)])]


def test_one_chip_counts_are_unchanged():
    """At tp 1 the counts are those the one-chip yardstick always gave,
    exactly (worked out before tensor parallelism was counted)."""
    out = tick_totals(Shapes(**QWEN3_4B), TICKS, 8, 16, 197e12, 819e9)
    assert out == {"gemm_least_s": 0.04716354307698555,
                   "gemm_flops": 7789829160960.0,
                   "gemm_bytes": 15666610176.0,
                   "gemm_compute_bound_s": 0.037771730153908625,
                   "attn_least_s": 0.0005739801318681318,
                   "attn_compute_bound_s": 0.0,
                   "model_flops": 656781017088.0,
                   "real_tokens": 89.0, "positions": 1072.0}
    assert Shapes(**QWEN3_4B, tp=4).matmul_params == \
        Shapes(**QWEN3_4B).matmul_params


@pytest.mark.parametrize("tp", [2, 4])
def test_per_chip_shards_sum_to_the_whole(tp):
    one, many = Shapes(**QWEN3_4B), Shapes(**QWEN3_4B, tp=tp)
    # column-parallel (K, N/tp), row-parallel (K/tp, N)
    assert many.projections() == [
        (2560, 4096 // tp), (2560, 1024 // tp), (2560, 1024 // tp),
        (4096 // tp, 2560), (2560, 9728 // tp), (2560, 9728 // tp),
        (9728 // tp, 2560)]
    # operations and weight bytes (codes and scales: no rows, M = 0) of
    # every chip's shard add up to the whole projection's
    for (k, n), (kc, nc) in zip(one.projections(), many.projections()):
        f, b = gemm_work(16, k, n, 8, 32)
        fc, bc = gemm_work(16, kc, nc, 8, 32)
        assert fc * tp == f
        assert gemm_work(0, kc, nc, 8, 32)[1] * tp == \
            gemm_work(0, k, n, 8, 32)[1]
    # attention over n_heads/tp and n_kv_heads/tp heads, the same keys
    for off, q in ((99, 1), (16, 3), (0, 64)):
        f, b = attn_work(one, off, q, 16)
        fc, bc = attn_work(many, off, q, 16)
        assert (fc * tp, bc * tp) == (f, b)
    a = tick_totals(one, TICKS, 8, 16, 197e12, 819e9)
    c = tick_totals(many, TICKS, 8, 16, 197e12, 819e9)
    for k in ("gemm_flops", "model_flops", "real_tokens", "positions"):
        assert c[k] == a[k], k
    # the same work spread over tp chips: attention's least time summed
    # over the chips is one chip's; the GEMMs' reads of their activations
    # repeat on every chip, so theirs is no less
    assert c["attn_least_s"] == pytest.approx(a["attn_least_s"])
    assert c["gemm_least_s"] >= a["gemm_least_s"]
    assert c["gemm_bytes"] > a["gemm_bytes"]
