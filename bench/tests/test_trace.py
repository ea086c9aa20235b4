"""The trace reduction: busy time, idle share, kernel time, gap labels."""
from bench.lib import trace as tr
from bench.lib.trace import Event
from bench.metrics.attn_roofline import is_attn
from bench.metrics.gemm_roofline import is_gemm


def ev(name, s, e):
    return Event(name, float(s), float(e))


# Op names as a TPU trace gives them: the HLO instruction, with no kernel
# name in it (layouts and attributes shortened).
GEMM8 = ('%checkpoint.69 = f32[1024,9728]{1,0:T(8,128)} custom-call('
         'bf16[1024,2560]{1,0:T(8,128)(2,1)S(1)} %bitcast.217, '
         's8[2560,9728]{1,0:T(8,128)(4,1)} %fusion.33, s8[80,9728]{1,0} '
         '%fusion.34), custom_call_target="tpu_custom_call", '
         'frontend_attributes={kernel_metadata={}}')
GEMM4 = ('%custom-call.3 = f32[16,2560]{1,0} custom-call(bf16[16,9728]{1,0} '
         '%f, u8[9728,1280]{1,0} %p, s8[304,2560]{1,0} %s), '
         'custom_call_target="tpu_custom_call"')
ATTN = ('%custom-call.9 = f32[16,8,4,128]{3,2,1,0} custom-call(s32[16,132]'
        '{1,0} %bt, s32[16]{0} %cl, f32[16,8,4,128]{3,2,1,0} %q, '
        'bf16[1281,16,1024]{2,1,0} %k, bf16[1281,16,1024]{2,1,0} %v), '
        'custom_call_target="tpu_custom_call"')
ATTN_MQ = ('%custom-call.5 = f32[16,8,256,128]{3,2,1,0} custom-call('
           's32[16,132]{1,0} %bt, s32[16]{0} %qo, s32[16]{0} %ql, '
           'bf16[16,8,256,128]{3,2,1,0} %q, bf16[1281,16,1024]{2,1,0} %k, '
           'bf16[1281,16,1024]{2,1,0} %v), custom_call_target='
           '"tpu_custom_call"')
# The same call as a compiled module prints it: operand types only in the
# layout constraints.
ATTN_COMPILED = ('%_lambda_.1 = f32[16,8,4,128]{3,2,1,0:T(4,128)} custom-call('
                 '%bt.1, %cl.1, %bitcast.2, %k.1, %v.1), custom_call_target='
                 '"tpu_custom_call", operand_layout_constraints={s32[16,132]'
                 '{1,0}, s32[16]{0}, bf16[16,8,4,128]{3,2,1,0}, bf16[1281,16,'
                 '1024]{2,1,0}, bf16[1281,16,1024]{2,1,0}}, frontend_attributes'
                 '={kernel_metadata={}}')
OTHER = ['%fusion.63 = bf16[16,2560]{1,0} fusion(bf16[16,2560]{1,0} %a), '
         'kind=kLoop', '%convolution.2 = f32[16,151936]{1,0} convolution('
         'bf16[16,2560]{1,0} %h, bf16[2560,151936]{1,0} %e)',
         '%custom-call.1 = s8[2560,9728]{1,0} custom-call(s8[2560,9728]{1,0} '
         '%c), custom_call_target="tpu_custom_call"']


OPS = [ev("fusion.1", 0, 10), ev(GEMM8, 5, 30),      # overlapping
       ev(GEMM4, 40, 60), ev(ATTN, 60, 70), ev(ATTN_MQ, 90, 95),
       ev("copy", 120, 130)]
HOST = [ev("bench.traced", 0, 100), ev("bench.hook", 72, 88)]
WINDOW = (0.0, 100.0)


def test_busy_and_idle():
    # union inside the window: [0,30] + [40,70] + [90,95] = 65 ns
    assert tr.busy_ns(OPS, WINDOW) == 65.0
    assert tr.idle_gaps(OPS, WINDOW) == [(30.0, 40.0), (70.0, 90.0),
                                         (95.0, 100.0)]


def test_kernel_time_by_name():
    assert tr.kernel_ns(OPS, is_gemm, WINDOW) == 25 + 20
    # a short op name whose statistics carry the HLO instruction
    named = [Event("checkpoint.69", 0.0, 10.0, "checkpoint.69 " + GEMM8)]
    assert tr.kernel_ns(named, is_gemm, WINDOW) == 10
    assert tr.kernel_ns(OPS, is_attn, WINDOW) == 15
    # clipped to the window
    assert tr.kernel_ns(OPS, is_gemm, (10.0, 100.0)) == 20 + 20
    # each kernel by its signature, and nothing else
    assert is_gemm(GEMM8) and is_gemm(GEMM4)
    assert is_attn(ATTN) and is_attn(ATTN_MQ) and is_attn(ATTN_COMPILED)
    assert not any(is_gemm(t) for t in [ATTN, ATTN_MQ] + OTHER)
    assert not any(is_attn(t) for t in [GEMM8, GEMM4] + OTHER)
    assert tr.short_name(GEMM8) == ('%checkpoint.69 = f32[1024,9728] '
                                    'custom-call(bf16[1024,2560], '
                                    's8[2560,9728], s8[80,9728])')


def test_gaps_are_named_by_the_host_span_over_them():
    gaps = tr.top_gaps(OPS, HOST, WINDOW, n=2)
    assert gaps[0] == ("bench.hook", 20e-9)
    # the traced window's own span names no gap
    assert gaps[1] == ("inside generate", 10e-9)
    assert tr.label((200.0, 210.0), HOST) == "inside generate"


def test_top_ops():
    top = dict(tr.top_ops(OPS, WINDOW))
    assert top[tr.short_name(GEMM8)] == 25e-9 and "copy" not in top
    # a loop that holds the kernels, or an asynchronous copy's start,
    # is not an operation of its own
    held = OPS + [ev("%while.5 = (s32[], bf16[16,1,2560]) while((s32[], "
                     "bf16[16,1,2560]) %t)", 0, 100),
                  ev("%copy-start.3 = (bf16[2560], bf16[2560], u32[]) "
                     "copy-start(bf16[2560] %g)", 0, 100)]
    assert dict(tr.top_ops(held, WINDOW)) == top
