"""The program's names in a trace (``chipbench/progtrace.py``): the
engine's ``flix:`` spans in a CPU profiler trace of ``apply_ops_safe``,
the scope of each op from a compiled program's text, the reductions on
small synthetic traces whose answers are known, and the reader
``fused_fence_rows_ms.store``."""

from __future__ import annotations

import sys
from pathlib import Path
from types import SimpleNamespace

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT))

from chipbench import devtrace, harness, progtrace  # noqa: E402
from chipbench.devtrace import Event, Trace  # noqa: E402

MS = 1_000_000  # ns
DEV = "/device:TPU:0"
FUSED = "jit_flix_apply_pallas(7)"
KERNEL = '%flix_apply_pallas.1 = (s32[8]) custom-call(), custom_call_target="tpu_custom_call"'
# a compiled program's text as XLA prints it, cut to the lines that matter
HLO = """HloModule jit_flix_apply_pallas, is_scheduled=true

%body.1 (p: (s32[], s32[8])) -> (s32[], s32[8]) {
  %add.3 = s32[8]{0} add(%x, %y), metadata={op_name="jit(flix_apply_pallas)/flix.fused.fence_rows/jit(searchsorted)/while/body/add"}
}

ENTRY %main.9 (Arg_0.1: s32[8]) -> s32[8] {
  %while.79 = (s32[], s32[8]{0}) while(%tuple.2), condition=%cond.1, body=%body.1, metadata={op_name="jit(flix_apply_pallas)/flix.fused.fence_rows/jit(searchsorted)/while" source_file="x.py" source_line=3}
  %fusion.19 = s32[8]{0} fusion(%p), kind=kLoop, calls=%fc.1, metadata={op_name="jit(flix_apply_pallas)/flix.fused.route/gather"}
  %copy.4 = s32[8]{0} copy(%fusion.19)
  ROOT %flix_apply_pallas.1 = (s32[8]{0}) custom-call(%copy.4), custom_call_target="tpu_custom_call", metadata={op_name="jit(flix_apply_pallas)/pallas_call"}
}
"""


def hlo_event(instruction, start_ms, end_ms):
    return Event(f"%{instruction} = s32[8]{{0}} op()", start_ms * MS, end_ms * MS)


def fused_trace(spans=()):
    """Two batches of the fused program (0-10 ms, 20-30 ms), each a while of
    the fence rows holding a body op, a route fusion, a copy and the kernel;
    a ``%while.79`` of another program runs between them."""
    ops, modules = [], []
    for t in (0, 20):
        modules.append(Event(FUSED, t * MS, (t + 10) * MS))
        ops += [
            hlo_event("while.79", t, t + 4),
            hlo_event("add.3", t + 1, t + 2),  # the while's body
            hlo_event("fusion.19", t + 4, t + 5),
            hlo_event("copy.4", t + 5, t + 6),
            Event(KERNEL, (t + 6) * MS, (t + 9) * MS),
        ]
    modules.append(Event("jit_argsort(3)", 12 * MS, 14 * MS))
    ops.append(hlo_event("while.79", 12, 14))
    window = Event("cb:window", 0, 40 * MS)
    return Trace(ops={DEV: ops}, modules={DEV: modules}, spans=[window, *spans])


@pytest.mark.parametrize(
    "op_name, scope",
    [
        ("jit(f)/flix.fused.fence_rows/jit(searchsorted)/while", "flix.fused.fence_rows"),
        ("jit(_apply_ops_reference)/flix.reference.point/cond/branch_1_fun/gather",
         "flix.reference.point"),
        ("flix.reference.route", "flix.reference.route"),
        ("jit(f)/flix.fused.layout/flix.inner/add", "flix.inner"),  # innermost wins
        ("jit(flix_apply_pallas)/pallas_call", ""),
        ("jit(f)/flixy/add", ""),
        ("", ""),
    ],
)
def test_scope_is_the_innermost_flix_component(op_name, scope):
    assert progtrace.scope_of(op_name) == scope


def test_compiled_text_maps_instructions_to_scopes():
    assert progtrace.op_scopes(HLO) == {
        "add.3": "flix.fused.fence_rows",
        "while.79": "flix.fused.fence_rows",
        "fusion.19": "flix.fused.route",
    }
    assert progtrace.instruction(KERNEL) == "flix_apply_pallas.1"
    assert progtrace.instruction("jit_argsort(3)") == ""


def test_only_ops_inside_the_program_take_its_scopes():
    t = fused_trace()
    labels = progtrace.label_ops(t, DEV, {progtrace.PROGRAMS["fused"]: progtrace.op_scopes(HLO)})
    by_time = {(x.start // MS, progtrace.instruction(x.name)): s for x, s in labels.items()}
    assert by_time[(0, "while.79")] == "flix.fused.fence_rows"
    assert by_time[(12, "while.79")] == ""  # the same name in another program
    assert by_time[(25, "copy.4")] == ""  # no op_name
    assert by_time[(26, "flix_apply_pallas.1")] == ""
    # outermost ops only: the while's body op counts with the while
    assert progtrace.scope_ns(labels, 0, 40 * MS) == {
        "flix.fused.fence_rows": 8 * MS,
        "flix.fused.route": 2 * MS,
    }
    assert progtrace.scope_ns(labels, 15 * MS, 40 * MS) == {
        "flix.fused.fence_rows": 4 * MS,
        "flix.fused.route": 1 * MS,
    }


@pytest.mark.parametrize(
    "text, want",
    [(HLO, 4.0), (HLO.replace("flix.fused.", "phase."), None)],  # a program without scopes
    ids=["scoped", "unscoped"],
)
def test_fence_rows_reader_takes_device_ms_per_batch(monkeypatch, text, want):
    monkeypatch.setattr(progtrace, "executor_text", lambda cell, impl: text)
    run = SimpleNamespace(trace=fused_trace(), batches=[{}, {}], cell=None)
    assert harness.load_reader("fused_fence_rows_ms.store").read(run) == want


def test_fence_rows_reader_reads_nothing_without_a_device_trace():
    read = harness.load_reader("fused_fence_rows_ms.store").read
    assert read(SimpleNamespace(trace=None, batches=[{}], cell=None)) is None
    empty = Trace(ops={}, modules={}, spans=[Event("cb:window", 0, 10)])
    assert read(SimpleNamespace(trace=empty, batches=[{}], cell=None)) is None


PROGRAM_SPANS = [
    Event("flix:make_ops", 10 * MS, 12 * MS),
    Event("flix:apply_ops_safe", 14 * MS, 32 * MS),
    Event("flix:sync.has_updates", 14 * MS, 17 * MS),
    Event("flix:dispatch.fused", 17 * MS, 18 * MS),
    Event("flix:sync.needs_restructure", 18 * MS, 31 * MS),
    Event("flix:sync.needs_restructure", 35 * MS, 36 * MS),
    Event("flix:sync.has_updates", 45 * MS, 46 * MS),  # after the window
]
BENCH_SPANS = [
    Event("cb:generate", 30 * MS, 40 * MS),
    Event("cb:make_ops", 10 * MS, 13 * MS),
    Event("cb:apply_ops_safe", 13 * MS, 33 * MS),
]


def test_engine_idle_is_the_idle_time_inside_the_program_spans():
    t = fused_trace()
    # spans cover 10-12, 14-32 and 35-36 of the window; the device runs
    # 0-9, 12-14 and 20-29
    idle = progtrace.idle_in_spans_ns(t.ops[DEV], PROGRAM_SPANS, 0, 40 * MS)
    assert idle == 2 * MS + 9 * MS + 1 * MS
    assert progtrace.idle_in_spans_ns(t.ops[DEV], [], 0, 40 * MS) == 0
    assert progtrace.syncs(PROGRAM_SPANS, 0, 40 * MS) == 3


@pytest.mark.parametrize(
    "gap, name",
    [
        ((15 * MS, 16 * MS), "flix:sync.has_updates"),  # inside nested spans: the innermost
        ((16 * MS, 21 * MS), "flix:sync.needs_restructure"),  # 1 + 1 + 3 ms in three spans
        ((12_500_000, 14_500_000), "apply_ops_safe"),  # mostly between the flix: spans
        ((13 * MS, 14 * MS), "apply_ops_safe"),  # only cb:apply_ops_safe overlaps
        ((31 * MS, 33 * MS), "generate"),  # a tie of two cb: spans
        ((10 * MS, 12 * MS), "flix:make_ops"),
        ((37 * MS, 39 * MS), "generate"),
        ((41 * MS, 44 * MS), "host:outside-spans"),
    ],
)
def test_a_gap_takes_the_innermost_span_that_holds_most_of_it(gap, name):
    every = [Event("cb:window", 0, 50 * MS), *BENCH_SPANS, *PROGRAM_SPANS]
    assert progtrace.gap_name(gap, every) == name


def test_idle_time_goes_to_the_innermost_open_span():
    t = fused_trace()
    every = [t.spans[0], *BENCH_SPANS, *PROGRAM_SPANS]
    # the device idles 9-12, 14-20 and 29-40
    assert progtrace.idle_by_span(t.ops[DEV], every, 0, 40 * MS) == {
        "host:outside-spans": 1 * MS,
        "flix:make_ops": 2 * MS,
        "flix:sync.has_updates": 3 * MS,
        "flix:dispatch.fused": 1 * MS,
        "flix:sync.needs_restructure": 4 * MS,
        "generate": 9 * MS,
    }


def test_breakdown_names_ops_by_scope_and_gaps_by_the_innermost_span():
    t = fused_trace(BENCH_SPANS)
    labels = progtrace.label_ops(t, DEV, {progtrace.PROGRAMS["fused"]: progtrace.op_scopes(HLO)})
    b = progtrace.breakdown(t, PROGRAM_SPANS, labels, top=3)
    assert b["device_ops"][0] == [f"flix.fused.fence_rows {hlo_event('while.79', 0, 1).name}", 0.008]
    # the device idles 9-12, 14-20 and 29-40
    assert [n for n, _ in b["idle_gaps"]] == ["generate", "flix:sync.has_updates", "flix:make_ops"]
    assert [s for _, s in b["idle_gaps"]] == pytest.approx([0.011, 0.006, 0.003])


def test_existing_readers_read_the_same_with_program_spans_present():
    names = ("fused_kernel_ms.store", "fused_wrapper_ms.store", "device_idle_pct.store",
             "device_idle_pct.read", "reference_ms.store")
    plain = fused_trace(BENCH_SPANS)
    spanned = fused_trace(BENCH_SPANS + PROGRAM_SPANS)
    got = {}
    for t in (plain, spanned):
        run = SimpleNamespace(trace=t, batches=[{}, {}], peaks=None)
        got[id(t)] = [harness.load_reader(n).read(run) for n in names]
    assert got[id(plain)] == got[id(spanned)]
    assert got[id(plain)][:3] == [pytest.approx(3.0), pytest.approx(7.0), pytest.approx(50.0)]
    assert devtrace.busy_share(plain) == devtrace.busy_share(spanned)


# ---- a real trace on the CPU ---------------------------------------------


def record(tmp_path, fn):
    import jax

    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    return next(tmp_path.rglob("*.xplane.pb"))


def nested(inner, outer):
    return outer.start <= inner.start and inner.end <= outer.end


@pytest.mark.parametrize(
    "backend, has_updates, syncs_tags",
    [("tpu", None, True), ("tpu", False, False), ("cpu", True, False)],
    ids=["tags-read", "caller-says-no-updates", "caller-says-updates"],
)
def test_apply_ops_safe_writes_its_spans(tmp_path, monkeypatch, backend, has_updates, syncs_tags):
    """One read-only batch through the driver.  ``impl="auto"`` reads the
    tags only on a TPU, so the test says it is on one; the batch has no
    updates, so the reference executor runs either way."""
    import jax

    from repro.core import build
    from repro.core.ops import OP_POINT, apply_ops_safe, make_ops

    keys = np.arange(0, 512, 2, dtype=np.int32)
    state = build(keys, keys, node_size=8, nodes_per_bucket=4, fill=0.5)
    ops, _ = make_ops(np.full(16, OP_POINT, np.int32), keys[:16], keys[:16])
    apply_ops_safe(state, ops)  # compiled outside the trace
    monkeypatch.setattr(jax, "default_backend", lambda: backend)

    def batch():
        out = apply_ops_safe(state, make_ops(np.full(16, OP_POINT, np.int32), keys[:16])[0],
                             has_updates=has_updates)
        jax.block_until_ready(out[1])

    path = record(tmp_path, batch)
    spans = progtrace.load_spans(path)
    names = sorted(s.name for s in spans)
    want = ["flix:apply_ops_safe", "flix:dispatch.reference", "flix:make_ops",
            "flix:sync.needs_restructure"] + (["flix:sync.has_updates"] if syncs_tags else [])
    assert names == sorted(want)
    (outer,) = [s for s in spans if s.name == "flix:apply_ops_safe"]
    for s in spans:
        if s.name != "flix:make_ops":
            assert nested(s, outer), s.name
    # the benchmark's loader keeps its own spans only
    assert not [s for s in devtrace.load(path).spans if s.name.startswith("flix:")]


def test_a_restructure_replays_the_batch_inside_its_span(tmp_path):
    from repro.core import build
    from repro.core.ops import OP_INSERT, apply_ops_safe, make_ops

    keys = np.arange(0, 2048, 64, dtype=np.int32)
    state = build(keys, keys, node_size=4, nodes_per_bucket=2, fill=0.5)
    ins = np.arange(1, 41, dtype=np.int32)  # 40 inserts into one bucket of 8 slots
    ops, _ = make_ops(np.full(40, OP_INSERT, np.int32), ins, ins)
    out = {}
    path = record(tmp_path, lambda: out.update(stats=apply_ops_safe(state, ops)[2]))
    assert out["stats"]["restructure_retries"] == 1
    spans = sorted(progtrace.load_spans(path), key=lambda s: s.start)
    assert [s.name for s in spans] == [
        "flix:apply_ops_safe", "flix:dispatch.reference", "flix:sync.needs_restructure",
        "flix:restructure", "flix:dispatch.reference",
    ]
    assert nested(spans[4], spans[3]) and nested(spans[3], spans[0])


@pytest.mark.parametrize("impl", ["reference", "fused"])
def test_executor_text_is_the_program_the_cell_runs(impl):
    """Compiled from the cell's shapes alone, the executor is the one
    ``apply_ops_safe`` compiles for the built table (the fused one in
    interpret mode here)."""
    from repro.core import build
    from repro.core.config import ExecConfig
    from repro.core.ops import make_ops, plain_executor

    cell = harness.load_cell("store-mixed-4k")
    cell.config["log2_keys"] = 10
    cell.traffic["ops"] = {k: -(-v // 64) for k, v in cell.traffic["ops"].items()}
    nb, npb, ns, n = progtrace.executor_shapes(cell)
    keys = np.arange(1 << 10, dtype=np.int32) * 3
    g = cell.config["geometry"]
    state = build(keys, keys, node_size=g["node_size"], nodes_per_bucket=g["nodes_per_bucket"],
                  fill=g["fill"])
    assert state.geometry == (nb, npb, ns)
    ops, _ = make_ops(np.zeros(n, np.int32), keys[:n], keys[:n])
    cfg = ExecConfig().replace(donate=False, validate=False, validate_ranges=False)
    fn, args, kwargs = plain_executor(state, ops, impl=impl, cfg=cfg)
    want = fn.lower(*args, **kwargs).compile().as_text()
    assert progtrace.executor_text(cell, impl) == want
    assert f"flix.{impl}.route" in progtrace.op_scopes(want).values()


def test_named_reduces_one_trace_under_the_program_names(monkeypatch):
    import importlib

    monkeypatch.syspath_prepend(str(ROOT / "chipbench"))
    named = importlib.import_module("named")
    monkeypatch.setattr(progtrace, "executor_text", lambda cell, impl: HLO)
    line = named.named(fused_trace(BENCH_SPANS), PROGRAM_SPANS, None, batches=2)
    assert line["scoped_ms"] == {"flix.fused.fence_rows": 4.0, "flix.fused.route": 1.0}
    assert line["engine_idle_ms"] == 6.0
    assert line["host_syncs"] == 1.5
    assert sum(line["idle_ms"].values()) == pytest.approx(10.0)  # 20 ms idle in 2 batches
    assert next(iter(line["idle_ms"])) == "generate"
    assert [n for n, _ in line["breakdown"]["idle_gaps"]] == [
        "generate", "flix:sync.has_updates", "flix:make_ops",
    ]


def test_named_refuses_the_cpu():
    import os
    import subprocess

    proc = subprocess.run(
        [sys.executable, "chipbench/named.py", "--workload", "store-read-64k",
         "--seed", "1", "--seconds", "1"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert proc.returncode != 0 and proc.stdout == ""
    assert "named:" not in proc.stderr
