"""The trace reduction on traces whose numbers are known."""

from pathlib import Path

import pytest

from benchmarks.trace import reduce
from benchmarks.trace.reduce import Event

FIXTURE = (Path(__file__).resolve().parents[1] / "fixtures" / "profile"
           / "tiny.trace.json.gz")


def test_interval_arithmetic():
    assert reduce.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert reduce.total(reduce.clip([(0, 3), (5, 6)], 2, 5.5)) == 1.5
    assert reduce.subtract([(0, 10)], [(2, 3), (5, 7)]) == [
        (0, 2), (3, 5), (7, 10)]
    assert reduce.gaps([(1, 2)], 0, 3) == [(0, 1), (2, 3)]


def test_recorded_fixture_gives_known_numbers():
    r = reduce.reduce_profile(FIXTURE)
    # device 0 runs fusion.1 0-120 and 200-280, copy.2 300-350 (us); the
    # containers (jit_, while., numeric, SyncOnDone, *Module), the Steps line
    # and the host are not operations. Device 1 runs 40 us.
    assert r["device_planes"] == 2
    assert r["window_s"] == pytest.approx(350e-6)
    assert r["busy_s"] == pytest.approx((250e-6 + 40e-6) / 2)
    assert r["idle_pct"] == pytest.approx(100 * (1 - 145 / 350))
    assert r["breakdown"]["device_ops"] == [["fusion.1", pytest.approx(200e-6)],
                                            ["copy.2", pytest.approx(50e-6)]]
    assert r["breakdown"]["idle_gaps"] == [["unattributed",
                                            pytest.approx(100e-6)]]


def _op(name, start, dur, plane="/device:TPU:0", **args):
    return Event(plane, "XLA Ops", name, float(start), float(dur), args)


#: what train_cli.hlo_index reads off the compiled program's text
HLO_TEXT = """
  %fusion.1 = bf16[8,8]{1,0} fusion(%p0), kind=kLoop, metadata={op_name="jit(train_step)/jit(main)/fwd_bwd/dot_general" source_file="x.py"}
  %all-gather-start.1 = (bf16[4]) all-gather-start(%p1), metadata={op_name="jit(train_step)/jit(main)/fwd_bwd/all_gather"}
  %custom-call.7 = bf16[8,8] custom-call(%q, %k, %v), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jit(main)/fwd_bwd/jvp(flash_attention)/pallas_call"}
  %all-gather-done.1 = bf16[8] all-gather-done(%ags), metadata={op_name="jit(train_step)/jit(main)/fwd_bwd/all_gather"}
  ROOT %fusion.2 = bf16[8,8]{1,0} fusion(%p2), metadata={op_name="jit(train_step)/jit(main)/optimizer_update/mul"}
  %all-reduce.3 = f32[] all-reduce(%x), metadata={op_name="jit(train_step)/jit(main)/fwd_bwd/psum"}
"""


def hand_made_trace() -> list[Event]:
    """Three executions of one program, 1000 us apart. Each: 0-400 fwd_bwd
    compute; an async all-gather from 100 to 500 of which 400-500 is exposed;
    500-600 optimizer; a synchronous all-reduce 600-650 (all exposed); then
    the device idles 650-1000 while the host waits for data."""
    ev = []
    for k in range(3):
        t = 1000 * k
        ev.append(Event("/device:TPU:0", "XLA Modules", "jit_train_step(1)",
                        t, 650, {}))
        ev += [
            # as a TPU trace names them: the whole HLO instruction
            _op("%fusion.1 = bf16[8,8]{1,0} fusion(%p0), kind=kLoop", t, 100),
            _op("%all-gather-start.1 = (bf16[4]) all-gather-start(%p1)",
                t + 100, 5),
            _op("%custom-call.7 = bf16[8,8] custom-call(%q, %k, %v)",
                t + 105, 295),
            _op("%all-gather-done.1 = bf16[8] all-gather-done(%ags)",
                t + 400, 100),
            _op("%fusion.2 = bf16[8,8]{1,0} fusion(%p2)", t + 500, 100),
            _op("%all-reduce.3 = f32[] all-reduce(%x)", t + 600, 50),
            # the "Async XLA Ops" line overlaps compute and is not busy time
            Event("/device:TPU:0", "Async XLA Ops", "%copy-start.1 = x",
                  t + 640, 300, {}),
            # the host is not the device: nothing of it is busy time
            Event("/host:CPU", "python", "train", t, 1000, {}),
        ]
    return ev


def test_hand_made_trace_idle_gap_and_overlapped_collective():
    from benchmarks.drivers.train_cli import hlo_index
    hlo = hlo_index(HLO_TEXT)
    assert hlo["custom-call.7"]["pallas"] and not hlo["fusion.2"]["pallas"]
    r = reduce.reduce_events(hand_made_trace(), hlo=hlo, kernels=("pallas_call",),
                             gap_label="between steps")
    # the window is two whole periods: start of execution 1 to start of 3
    assert r["periods"] == 2
    assert r["window_s"] == pytest.approx(2000e-6)
    assert r["busy_s"] == pytest.approx(2 * 650e-6)
    assert r["idle_pct"] == pytest.approx(35.0)
    # per period: fwd_bwd = 100 + 5 + 295 + 100 + 50, optimizer = 100
    assert r["scope_ms"]["fwd_bwd"] == pytest.approx(0.550)
    assert r["scope_ms"]["optimizer_update"] == pytest.approx(0.100)
    assert r["kernel_ms"]["pallas_call"] == pytest.approx(0.295)
    assert r["kernel_calls"]["pallas_call"] == 1
    # all-gather spans 100-500 (start op to end of done), all-reduce 600-650;
    # compute covers 0-100, 105-400 and 500-600: exposed = 100-105, 400-500
    # and 600-650
    assert r["collective_ms"] == pytest.approx(0.450)
    assert r["collective_exposed_ms"] == pytest.approx(0.155)
    assert r["longest_gaps_s"] == [pytest.approx(350e-6)] * 2
    assert r["breakdown"]["idle_gaps"] == [["between steps",
                                            pytest.approx(700e-6)]]
    assert r["breakdown"]["device_ops"][0] == ["custom-call.7",
                                               pytest.approx(590e-6)]
    assert r["unscoped_ms"] == 0


def test_a_trace_without_device_operations_is_an_error():
    with pytest.raises(ValueError):
        reduce.reduce_events([Event("/host:CPU", "python", "x", 0, 1, {})])
