"""The reduction from a trace to per-layer metrics: idle share, kernel
time and roofline share, on hand-made events laid out as a TPU trace
lays them out (operations nested in a while loop, programs on their own
line, the harness's spans on the host thread)."""

import pytest

import bench_helpers  # noqa: F401  (puts bench/ on the path)
import harness
import traces


def _events(ops, spans=(), window=(0.0, 100.0), n_devices=1, modules=()):
    return traces.Events(ops=list(ops), modules=list(modules), spans=list(spans),
                         window=window, n_devices=n_devices)


def test_union_merges_overlaps_and_nesting():
    assert traces.union_ns([(0, 10), (5, 20), (30, 40), (32, 35)]) == 30
    assert traces.union_ns([]) == 0


def test_idle_share_by_device_and_window():
    # device 0 busy 0-40 (a while op with ops nested in it) and 60-80;
    # device 1 busy 10-30; window 0-100, so 60% busy on 0, 20% on 1
    ev = _events(
        [(0, "while", 0, 40), (0, "fusion", 5, 10), (0, "fusion", 60, 80),
         (1, "copy", 10, 30), (1, "late", 150, 160)],
        n_devices=2,
    )
    assert traces.busy_s(ev) == pytest.approx((60 + 20) / 2 / 1e9)
    assert traces.idle_share(ev) == pytest.approx(100 * (1 - 0.4))
    assert traces.idle_share(_events([])) is None


def test_kernel_time_roofline_and_breakdown():
    ev = _events(
        [(0, "faas_kernel_a", 0, 30), (0, "faas_kernel_a", 40, 50), (0, "sort", 55, 60)],
        spans=[(0, "bench.request", 0, 100), (1, "bench.sweep", 0, 100),
               (2, "np.lexsort", 60, 95)],
    )
    assert traces.op_seconds(ev, lambda n: "faas" in n) == pytest.approx(40e-9)
    ctx = harness.Context(ev, [{"work": 1.0, "kernel_bytes": {"faas_kernel": 819}}],
                          {"hbm_bytes_per_s": 819e9})
    share = harness.reader("faas_kernel_roofline").read(ctx)
    assert share == pytest.approx(100 * 1e-9 / 40e-9)
    assert traces.top_ops(ev)[0] == ["faas_kernel_a", pytest.approx(40e-9)]
    gaps = traces.idle_gaps(ev)
    assert gaps[0][0] == "bench.request > np.lexsort"
    assert gaps[0][1] == pytest.approx(40e-9)


def test_roofline_reader_raises_where_the_kernel_ran_unseen():
    ev = _events([(0, "sort", 0, 10)], spans=[(0, "bench.request", 0, 100)])
    peaks = {"hbm_bytes_per_s": 819e9}
    ran = harness.Context(ev, [{"work": 1.0, "kernel_bytes": {"faas_kernel": 819}}], peaks)
    with pytest.raises(RuntimeError, match="renamed"):
        harness.reader("faas_kernel_roofline").read(ran)
    idle = harness.Context(ev, [{"work": 0.0, "kernel_bytes": {}}], peaks)
    assert harness.reader("faas_kernel_roofline").read(idle) is None


def test_scan_ms_per_tick():
    ev = _events([(0, "while", 0, 10)],
                 spans=[(0, "bench.request", 0, 50), (1, "bench.tick", 5, 50),
                        (0, "bench.request", 50, 100), (1, "bench.tick", 55, 100)],
                 modules=[(0, "jit__simulate_sweep(1)", 0, 10), (0, "jit__simulate_sweep(1)", 60, 80),
                          (0, "jit_sort(2)", 80, 90)])
    ms = harness.reader("scan_device_ms.tick").read(harness.Context(ev, [], {}))
    assert ms == pytest.approx(1e3 * 30e-9 / 2)


def test_kernel_time_from_custom_calls_inside_its_launcher():
    # a trace that names the kernel's op only as a custom call: the time is
    # that of the custom calls inside the launcher's programs, per device
    ev = _events(
        [(0, "%custom-call.3 = f32[8] custom-call()", 10, 30),
         (0, "%custom-call.9 = f32[8] custom-call()", 60, 70),
         (1, "%custom-call.3 = f32[8] custom-call()", 12, 20),
         (0, "%fusion.1 = f32[8] fusion()", 30, 40)],
        modules=[(0, "jit_faas_sweep_pallas(7)", 5, 45), (0, "jit_sort(2)", 55, 75),
                 (1, "jit_faas_sweep_pallas(7)", 0, 50)],
        n_devices=2,
    )
    assert traces.kernel_seconds(ev, "faas_kernel", "faas_sweep_pallas") == pytest.approx(28e-9)
    assert traces.kernel_seconds(ev, "fleet_kernel", "fleet_sweep_pallas") == 0


def test_events_round_trip_through_json(tmp_path):
    ev = _events([(0, "%while.5", 1.0, 9.0)], spans=[(0, "bench.request", 0.0, 10.0)],
                 modules=[(0, "jit__simulate_sweep(3)", 1.0, 9.0)], window=(0.0, 10.0))
    path = str(tmp_path / "t.events.json.gz")
    ev.to_json(path)
    assert traces.Events.from_json(path) == ev
