"""The program's own names in a profiler trace, beside ``devtrace``'s reduction.

The engine writes host spans named ``flix:<step>`` (``repro.core.trace``)
and names its device phases with ``jax.named_scope``: ``flix.fused.*`` in
the fused executor's wrapper, ``flix.reference.*`` in the reference
executor.  ``devtrace.load`` keeps only the benchmark's ``cb:`` spans and
each op's HLO text, and on a v5e that text carries no op metadata.  So
this module reads:

  * ``load_spans``: the ``flix:`` host spans of a ``.xplane.pb`` file;
  * ``op_scopes``: each instruction's scope, from the ``op_name`` metadata
    in a compiled program's text.  ``executor_text`` compiles the cell's
    executor again from its shapes alone, afresh (``_compiled_text``);
  * ``label_ops``: each op event of a device with its scope.  Only the ops
    that run inside one of the program's runs (``XLA Modules`` events) get
    one, since instruction names are unique within a program only.

The reductions on top are interval arithmetic on the trace's one clock, as
in ``devtrace``.
"""

from __future__ import annotations

import bisect
import functools
import re

from chipbench import devtrace
from chipbench.devtrace import Event

SPAN_PREFIX = "flix:"
SYNC_PREFIX = "flix:sync."
# the executors' programs on a device's XLA Modules line, by the impl that runs them
PROGRAMS = {"fused": r"^jit_flix_apply_pallas\(", "reference": r"^jit__apply_ops_reference\("}

_SCOPE = re.compile(r"(?:^|/)(flix\.[\w.]+)(?=/|$)")
_INSTRUCTION = re.compile(r"^%([^\s=]+) = ")
_OP_NAME = re.compile(r'^\s*(?:ROOT )?%([^\s=]+) = [^\n]*?\bop_name="([^"]*)"', re.M)


def scope_of(op_name: str) -> str:
    """The innermost ``flix.*`` component of an op's name stack, or ``""``."""
    found = _SCOPE.findall(op_name)
    return found[-1] if found else ""


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> scope, for every instruction of a compiled
    program's text whose ``op_name`` holds a ``flix.*`` component."""
    out = {}
    for instruction, op_name in _OP_NAME.findall(hlo_text):
        scope = scope_of(op_name)
        if scope:
            out[instruction] = scope
    return out


def instruction(event_name: str) -> str:
    """The instruction name of an op event (``%while.79 = ...`` -> ``while.79``)."""
    m = _INSTRUCTION.match(event_name)
    return m.group(1) if m else ""


def executor_shapes(cell) -> tuple[int, int, int, int]:
    """``(num_buckets, nodes_per_bucket, node_size, batch_ops)`` of the
    table a store cell builds and of each batch it sends."""
    from repro.core.build import plan_geometry

    g = cell.config["geometry"]
    nb, npb, ns = plan_geometry(
        1 << int(cell.config["log2_keys"]),
        node_size=g["node_size"], nodes_per_bucket=g["nodes_per_bucket"], fill=g["fill"],
    )
    return nb, npb, ns, sum(cell.traffic["ops"].values())


def executor_text(cell, impl: str) -> str:
    """The compiled text of the executor ``impl`` that ``apply_ops_safe``
    runs for the cell's batches on its first table (a window that
    restructures runs a second geometry, which this does not compile)."""
    return _compiled_text(impl, *executor_shapes(cell))


@functools.lru_cache(maxsize=4)
def _compiled_text(impl: str, nb: int, npb: int, ns: int, n: int) -> str:
    """A fresh compile.  JAX keys its persistent compile cache on the
    program without its op metadata, so an entry that another checkout's
    program wrote (the same program with other scopes, or none) would
    come back with that program's names: the cache is off here.  A debug
    option left at its default keeps JAX from handing back the executable
    set-up compiled in this process."""
    import jax
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache

    from repro.core.config import ExecConfig
    from repro.core.ops import OpBatch, plain_executor
    from repro.core.state import FliXState

    def i32(*shape):
        return jax.ShapeDtypeStruct(shape, jnp.int32)

    state = FliXState(
        keys=i32(nb, npb, ns), vals=i32(nb, npb, ns), node_count=i32(nb, npb),
        node_max=i32(nb, npb), num_nodes=i32(nb), mkba=i32(nb),
        needs_restructure=jax.ShapeDtypeStruct((), jnp.bool_),
    )
    ops = OpBatch(tag=i32(n), key=i32(n), val=i32(n))
    cfg = ExecConfig().replace(donate=False, validate=False, validate_ranges=False)
    fn, args, kwargs = plain_executor(state, ops, impl=impl, cfg=cfg)
    cached = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        lowered = fn.lower(*args, **kwargs)
        return lowered.compile({"xla_dump_hlo_as_text": False}).as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", cached)
        compilation_cache.reset_cache()


def label_ops(trace, device: str, programs: dict[str, dict[str, str]]) -> dict[Event, str]:
    """Each op event of ``device`` -> its scope (``""`` for none).
    ``programs`` maps a regular expression on the XLA Modules line to the
    scopes of that program's instructions (``op_scopes``)."""
    ops = sorted(trace.ops[device], key=lambda x: x.start)
    starts = [x.start for x in ops]
    out = dict.fromkeys(ops, "")
    for pattern, scopes in programs.items():
        rx = re.compile(pattern)
        for run in trace.modules[device]:
            if not rx.search(run.name):
                continue
            i = bisect.bisect_left(starts, run.start)
            j = bisect.bisect_left(starts, run.end)
            for x in ops[i:j]:
                out[x] = scopes.get(instruction(x.name), "")
    return out


def scope_ns(labels: dict[Event, str], lo: int, hi: int) -> dict[str, int]:
    """Scope -> summed durations of the outermost ops with that label that
    start in ``[lo, hi)`` (a ``while`` op's body ops lie inside it and
    count with it)."""
    out: dict[str, int] = {}
    for x in devtrace.outermost(labels):
        if lo <= x.start < hi and labels[x]:
            out[labels[x]] = out.get(labels[x], 0) + x.dur
    return out


def load_spans(path) -> list[Event]:
    """The program's ``flix:`` host spans in one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData

    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        start = int(e.start_ns)
                        spans.append(Event(e.name, start, start + int(e.duration_ns)))
    return spans


def _overlap_ns(a, b) -> int:
    """Nanoseconds two lists of disjoint ascending intervals share."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        total += max(0, e - s)
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def idle_in_spans_ns(ops, spans, lo: int, hi: int) -> int:
    """Nanoseconds of ``[lo, hi)`` inside the union of ``spans`` in which
    no op of ``ops`` runs."""
    inside = devtrace.union(((x.start, x.end) for x in spans), lo, hi)
    busy = devtrace.union(((x.start, x.end) for x in ops), lo, hi)
    return sum(e - s for s, e in inside) - _overlap_ns(inside, busy)


def syncs(spans, lo: int, hi: int) -> int:
    """The ``flix:sync.*`` spans that start in ``[lo, hi)``: blocking
    device-to-host reads of the engine's host driver."""
    return sum(1 for x in spans if x.name.startswith(SYNC_PREFIX) and lo <= x.start < hi)


def _held(gap: tuple[int, int], spans) -> dict[str, int]:
    """Span name -> ns of ``gap`` in which it was the shortest span open:
    a nested ``flix:`` span before the ``cb:`` span around it.  Instants
    under no span go to ``host:outside-spans``."""
    s, e = gap
    open_ = [sp for sp in spans if sp.start < e and sp.end > s]
    cuts = sorted({s, e, *(max(s, sp.start) for sp in open_), *(min(e, sp.end) for sp in open_)})
    held: dict[str, int] = {}
    for a, b in zip(cuts, cuts[1:]):
        inner = [sp for sp in open_ if sp.start <= a and b <= sp.end]
        name = min(inner, key=lambda sp: sp.dur).name if inner else "host:outside-spans"
        held[name] = held.get(name, 0) + b - a
    return held


def _named(name: str) -> str:
    """``cb:`` names lose their prefix, as in ``devtrace.breakdown``;
    ``flix:`` names keep theirs."""
    return name.removeprefix(devtrace.SPAN_PREFIX)


def _candidates(spans):
    """The spans other than the window, sorted by start, and a function
    that gives those of them that may overlap ``[s, e)``."""
    spans = sorted((sp for sp in spans if sp.name != devtrace.WINDOW_SPAN), key=lambda sp: sp.start)
    starts = [sp.start for sp in spans]
    longest = max((sp.dur for sp in spans), default=0)

    def near(s, e):
        return spans[bisect.bisect_left(starts, s - longest) : bisect.bisect_left(starts, e)]

    return near


def gap_name(gap: tuple[int, int], spans) -> str:
    """The span in which the host spent most of an idle gap (``_held``)."""
    held = _held(gap, _candidates(spans)(*gap))
    return _named(max(held, key=held.get))


def idle_by_span(ops, spans, lo: int, hi: int) -> dict[str, int]:
    """Span name -> ns of ``[lo, hi)`` in which no op of ``ops`` ran and
    that span was the shortest open (``_held``)."""
    near = _candidates(spans)
    out: dict[str, int] = {}
    for gap in devtrace.gaps(ops, lo, hi):
        for name, ns in _held(gap, near(*gap)).items():
            out[_named(name)] = out.get(_named(name), 0) + ns
    return out


def breakdown(trace, spans, labels: dict[Event, str], top: int = 10) -> dict:
    """``devtrace.breakdown`` under the program's names: each outermost op
    of the first device as ``<scope> <HLO text>`` where it has a scope,
    and each idle gap by ``gap_name`` over the benchmark's spans and
    ``spans``."""
    lo, hi = trace.window
    devs = trace.devices
    if not devs:
        return {"device_ops": [], "idle_gaps": []}
    by_name: dict[str, int] = {}
    for x in devtrace.outermost(trace.ops[devs[0]]):
        if lo <= x.start < hi:
            scope = labels.get(x, "")
            name = devtrace.short_name(f"{scope} {x.name}" if scope else x.name)
            by_name[name] = by_name.get(name, 0) + x.dur
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    every = trace.spans + list(spans)
    longest = sorted(devtrace.gaps(trace.ops[devs[0]], lo, hi), key=lambda g: g[0] - g[1])
    return {
        "device_ops": [[n, ns / 1e9] for n, ns in ops],
        "idle_gaps": [[gap_name(g, every), (g[1] - g[0]) / 1e9] for g in longest[:top]],
    }
