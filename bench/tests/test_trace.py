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


# ---- the cell's own chips, the program's spans, collectives ---------------
def plane(name, lines):
    """A ``ProfileData`` plane: ``lines`` maps a line name to (name,
    start_ns, end_ns) events."""
    from types import SimpleNamespace as NS
    return NS(name=name, lines=[
        NS(name=ln, events=[NS(name=n, start_ns=float(s), end_ns=float(e),
                               stats={}) for n, s, e in evs])
        for ln, evs in lines.items()])


ALL_REDUCE = ('%all-reduce.7 = bf16[16,2560]{1,0} all-reduce(bf16[16,2560]'
              '{1,0} %fusion.3), channel_id=1, replica_groups={{0,1,2,3}}, '
              'to_apply=%add.1')
GATHER_START = ('%all-gather-start.2 = (bf16[16,38016]{1,0}, bf16[16,'
                '151936]{1,0}) all-gather-start(bf16[16,38016]{1,0} '
                '%convolution.2), channel_id=2, dimensions={1}')
GATHER_DONE = ('%all-gather-done.2 = bf16[16,151936]{1,0} all-gather-done(('
               'bf16[16,38016]{1,0}, bf16[16,151936]{1,0}) '
               '%all-gather-start.2)')
NOT_COLLECTIVE = [GEMM8, ATTN, '%fusion.9 = bf16[16,2560]{1,0} fusion('
                  'bf16[16,2560]{1,0} %all-reduce.7), kind=kLoop',
                  '%copy-start.3 = (bf16[2560], bf16[2560], u32[]) '
                  'copy-start(bf16[2560] %g)'] + OTHER


def test_planes_outside_the_cells_devices_are_ignored():
    planes = [plane("/device:TPU:0", {"XLA Ops": [(GEMM8, 0, 10)]}),
              plane("/device:TPU:1", {"XLA Ops": [(GEMM8, 0, 90)]}),
              plane("/device:TPU:2", {"XLA Ops": [(ATTN, 5, 50)]}),
              plane("/host:CPU", {"python": [
                  ("bench.traced", 0, 100), ("engine.tick", 0, 60),
                  ("engine.fetch", 20, 30), ("jit_mixed_step", 5, 8)]})]
    red = tr.reduce_planes(planes, WINDOW, [0])
    assert list(red.ops) == ["/device:TPU:0"]
    # the harness's and the program's spans, nothing else of the host
    assert [e.name for e in red.host] == ["bench.traced", "engine.tick",
                                          "engine.fetch"]
    two = tr.reduce_planes(planes, WINDOW, [0, 2])
    assert sorted(two.ops) == ["/device:TPU:0", "/device:TPU:2"]
    assert tr.device_id("/device:TPU:3") == 3
    assert tr.device_id("/host:CPU") is None


def test_idle_gaps_name_the_innermost_engine_phase():
    host = HOST + [ev("engine.tick", 25, 100), ev("engine.retire", 28, 45),
                   ev("engine.fetch", 71, 89)]
    gaps = dict((round(s * 1e9), n) for n, s in
                tr.top_gaps(OPS, sorted(host, key=lambda e: e.start),
                            WINDOW))
    # 70-90: bench.hook (72-88) is shorter than engine.fetch (71-89)
    assert gaps == {20: "bench.hook", 10: "engine.retire",
                    5: "engine.tick"}


def test_collectives_by_opcode():
    assert tr.is_collective(ALL_REDUCE)
    assert tr.is_collective(GATHER_START) and tr.is_collective(GATHER_DONE)
    assert tr.is_collective("all-reduce.3") and tr.is_collective(
        "reduce-scatter.1") and tr.is_collective("collective-permute-done")
    assert tr.is_collective("all-reduce-scatter-fusion.2")
    # an op whose operand is a collective's result is none itself
    assert not any(tr.is_collective(t) for t in NOT_COLLECTIVE)


def test_collective_share_over_the_cells_chips():
    from types import SimpleNamespace as NS
    from bench.metrics.collective_share import read
    ops = {"/device:TPU:0": [ev(GEMM8, 0, 40), ev(ALL_REDUCE, 40, 50),
                             ev(GATHER_START, 60, 61),
                             ev(GATHER_DONE, 61, 70)],
           "/device:TPU:1": [ev(GEMM8, 0, 45), ev(ALL_REDUCE, 45, 50),
                             ev(GATHER_DONE, 95, 120)]}
    red = tr.Reduced(ops=ops, host=[], window=WINDOW)
    # chip 0: 10 + 1 + 9 = 20 ns; chip 1: 5 + 5 (clipped at 100) = 10 ns;
    # averaged over the two chips, over the 100 ns window
    assert read(NS(trace=red)) == 15.0
    # one chip, no collective: no reading (never 0)
    one = tr.Reduced(ops={"/device:TPU:0": OPS}, host=[], window=WINDOW)
    assert read(NS(trace=one)) is None
    assert read(NS(trace=None)) is None


def test_step_mfu_divides_by_the_cells_chips():
    from types import SimpleNamespace as NS
    from bench.metrics.step_mfu import read
    red = tr.Reduced(ops={}, host=[], window=(0.0, 2e9))
    run = NS(traced={"model_flops": 1e12}, trace=red,
             peaks={"flops_bf16": 1e12}, shapes=NS(tp=1))
    assert read(run) == 50.0
    run.shapes.tp = 4
    assert read(run) == 12.5
