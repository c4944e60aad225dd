"""The trace reducer (bench/trace.py) on a small trace made by hand."""
import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", ".."))

from bench.trace import Device, Trace, parse_kernel  # noqa: E402

KERNEL = ('%k.1 = f32[2,4,8,128]{3,2,1,0} custom-call(f32[2,4,1,16,128]{4,3,'
          '2,1,0} %a, f32[2,3,3,128,128]{4,3,2,1,0} %b), '
          'custom_call_target="tpu_custom_call"')


def small_trace() -> Trace:
    """A 100 ns window: two programs on the device, a while op covering
    its body's ops, and host spans around them."""
    ops = [(0, 30, "%while.1 = (s32[]) while(...)"),
           (5, 10, KERNEL),                     # inside the while
           (20, 25, "%fusion.2 = f32[8] fusion(...)"),
           (50, 60, "%copy.3 = f32[8] copy(...)"),
           (55, 70, "%fusion.4 = f32[8] fusion(...)"),
           (90, 95, KERNEL)]
    modules = [(0, 30, "jit_local_update"), (50, 70, "jit_stage"),
               (90, 95, "jit_local_update")]
    spans = [(0, 100, "bench.round"), (0, 45, "bench.run_round"),
             (45, 100, "bench.evaluate"), (72, 88, "bench.server_update")]
    return Trace((0, 100), [Device(ops, modules)], spans)


def test_busy_is_the_union_of_op_intervals():
    t = small_trace()
    assert t.devices[0].busy_intervals() == [(0, 30), (50, 70), (90, 95)]
    assert t.busy_s() == pytest.approx(55e-9)
    assert t.window_s == pytest.approx(100e-9)
    assert 1 - t.busy_s() / t.window_s == pytest.approx(0.45)


def test_idle_gaps_are_labelled_by_the_innermost_host_span():
    gaps = small_trace().idle_gaps()
    assert gaps == [(pytest.approx(20e-9), "bench.run_round"),
                    (pytest.approx(20e-9), "bench.server_update"),
                    (pytest.approx(5e-9), "bench.evaluate")]


def test_program_time_and_top_ops():
    t = small_trace()
    assert t.devices[0].module_seconds() == {
        "jit_local_update": pytest.approx(35e-9),
        "jit_stage": pytest.approx(20e-9)}
    top = t.top_ops(3)
    assert top[0] == ["jit_local_update:%while.1", pytest.approx(30e-9)]
    assert top[1] == ["jit_stage:%fusion.4", pytest.approx(15e-9)]
    assert t.rounds == 1


def test_kernel_calls_and_their_summed_time():
    ks = small_trace().kernels()
    assert len(ks) == 2
    assert sum(k.seconds for k in ks) == pytest.approx(10e-9)
    assert ks[0].operands == [(2, 4, 1, 16, 128), (2, 3, 3, 128, 128)]
    assert ks[0].results == [(2, 4, 8, 128)]
    assert parse_kernel(KERNEL, 1.0).seconds == 1.0


def test_busy_is_averaged_over_devices():
    t = small_trace()
    other = Device([(0, 100, "%x = f32[] fusion()")], [])
    two = Trace(t.window, [t.devices[0], other], t.spans)
    assert two.busy_s() == pytest.approx((55e-9 + 100e-9) / 2)


RECORDED = os.path.join(os.path.dirname(__file__), "data",
                        "small_tpu.xplane.pb")


def test_recorded_tpu_trace():
    """A trace recorded on a v5e: two rounds, each a grouped_conv forward
    and a kd_kl forward and backward under ``bench.run_round``, then 20 ms
    of host sleep and a small program under ``bench.evaluate``.  The
    device's clock runs about 1 ms ahead of the host's in this trace, so
    the window, cut by the host's round spans, holds the second round's
    calls only."""
    from bench.trace import reduce

    t = reduce(RECORDED, 1)
    assert t.rounds == 2
    assert 0.04 < t.window_s < 0.05
    assert 0 < t.busy_s() < 1e-4
    kinds = sorted((len(k.operands), k.results[0]) for k in t.kernels())
    assert kinds == [(2, (2, 4, 80, 128)), (2, (256,)), (5, (256, 1024))]
    gaps = sorted(t.idle_gaps(), reverse=True)
    assert [label for _, label in gaps[:2]] == ["bench.evaluate"] * 2
    assert gaps[0][0] > 0.02
    assert sum(sec for sec, _ in gaps) == pytest.approx(
        t.window_s - t.busy_s())
    assert set(t.devices[0].module_seconds()) == {"jit__lambda"}
