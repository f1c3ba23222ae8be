"""The benchmark's reduction from a trace to busy time, op time and gaps,
on small synthetic traces whose answers are known."""

from __future__ import annotations

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import devtrace  # noqa: E402
from chipbench.devtrace import Event, Trace  # noqa: E402

MS = 1_000_000  # ns


def trace_of(ops, spans, modules=()):
    dev = "/device:TPU:0"
    return Trace(ops={dev: list(ops)}, modules={dev: list(modules)}, spans=list(spans))


def test_union_merges_overlaps_and_clips_to_the_window():
    iv = [(0, 10), (5, 20), (30, 40), (38, 45), (100, 200)]
    assert devtrace.union(iv, 2, 120) == [(2, 20), (30, 45), (100, 120)]


@pytest.mark.parametrize(
    "ops, busy_ms",
    [
        ([], 0),
        ([Event("a", 10 * MS, 30 * MS)], 20),
        # nested ops (a while loop and its body) count once
        ([Event("while", 10 * MS, 50 * MS), Event("body", 20 * MS, 30 * MS)], 40),
        # ops sticking out of the window are clipped to it
        ([Event("a", -5 * MS, 10 * MS), Event("b", 90 * MS, 130 * MS)], 20),
    ],
)
def test_busy_share_is_the_union_over_the_window(ops, busy_ms):
    t = trace_of(ops, [Event("cb:window", 0, 100 * MS)])
    busy, window = devtrace.busy_share(t)
    assert window == pytest.approx(0.1)
    assert busy == pytest.approx(busy_ms / 1000)


def test_gaps_are_the_complement_of_busy_time():
    ops = [Event("a", 10, 20), Event("b", 15, 30), Event("c", 50, 60)]
    assert devtrace.gaps(ops, 0, 100) == [(0, 10), (30, 50), (60, 100)]
    g = sum(e - s for s, e in devtrace.gaps(ops, 0, 100))
    assert g + devtrace.busy_ns(ops, 0, 100) == 100


def test_kernel_time_sums_matching_events_in_the_window_only():
    kernel = '%flix_apply_pallas.1 = (s32[8,2,512]) custom-call(), custom_call_target="tpu_custom_call"'
    ops = [
        Event(kernel, 10, 40),
        Event("%fusion.3 = s32[64] fusion()", 40, 45),
        Event(kernel, 50, 70),
        Event(kernel, 150, 170),  # starts after the window closed
    ]
    pattern = r"^%flix_apply_pallas[.0-9]* = .*tpu_custom_call"
    assert devtrace.matching_ns(ops, pattern, 0, 100) == 50
    assert devtrace.matching_ns(ops, r"^%fusion", 0, 100) == 5
    assert devtrace.matching_ns(ops, r"nothing", 0, 100) == 0


def test_breakdown_counts_outermost_ops_and_names_gaps_by_host_span():
    ops = [
        Event("%while.1 = loop", 0, 40 * MS),
        Event("%fusion.2 = body", 5 * MS, 35 * MS),  # inside the while
        Event("%kernel = custom-call", 60 * MS, 90 * MS),
    ]
    spans = [
        Event("cb:window", 0, 100 * MS),
        Event("cb:make_ops", 40 * MS, 58 * MS),
        Event("cb:apply_ops_safe", 58 * MS, 100 * MS),
    ]
    b = devtrace.breakdown(trace_of(ops, spans))
    assert b["device_ops"] == [["%while.1 = loop", 0.04], ["%kernel = custom-call", 0.03]]
    assert b["idle_gaps"] == [["make_ops", 0.02], ["apply_ops_safe", 0.01]]


def test_a_trace_without_a_device_reads_nothing():
    t = Trace(ops={}, modules={}, spans=[Event("cb:window", 0, 10)])
    assert t.devices == []
    assert devtrace.busy_share(t) == (0.0, 1e-8)
    assert devtrace.breakdown(t) == {"device_ops": [], "idle_gaps": []}


def test_window_must_be_recorded_once():
    with pytest.raises(ValueError):
        trace_of([], []).window


def test_readers_take_kernel_wrapper_and_reference_time_per_batch():
    """The per-layer readers on a trace named as a v5e names them: two
    batches, each a fused program of 10 ms holding a 6-ms kernel."""
    from types import SimpleNamespace

    from chipbench import harness

    kernel = '%flix_apply_pallas.1 = (s32[8]) custom-call(), custom_call_target="tpu_custom_call"'
    ops = [Event(kernel, 1 * MS, 7 * MS), Event(kernel, 21 * MS, 27 * MS)]
    modules = [
        Event("jit_flix_apply_pallas(123)", 0, 10 * MS),
        Event("jit_flix_apply_pallas(123)", 20 * MS, 30 * MS),
        Event("jit__apply_ops_reference(9)", 40 * MS, 45 * MS),
    ]
    t = trace_of(ops, [Event("cb:window", 0, 50 * MS)], modules)
    run = SimpleNamespace(trace=t, batches=[{}, {}], peaks=None)
    read = {n: harness.load_reader(n).read(run) for n in (
        "fused_kernel_ms.store", "fused_wrapper_ms.store", "reference_ms.store",
        "device_idle_pct.store", "device_idle_pct.read", "fused_roofline.store")}
    assert read["fused_kernel_ms.store"] == pytest.approx(6.0)
    assert read["fused_wrapper_ms.store"] == pytest.approx(4.0)
    assert read["reference_ms.store"] == pytest.approx(2.5)
    assert read["device_idle_pct.store"] == pytest.approx(76.0)
    assert read["device_idle_pct.read"] == pytest.approx(76.0)
    assert read["fused_roofline.store"] is None  # no peaks: nothing to read
