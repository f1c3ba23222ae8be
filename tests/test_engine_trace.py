"""The engine's names in a trace: each executor's compiled program carries
every phase scope in its ops' ``op_name`` metadata, under the program name
it had before the scopes, and the host spans have fixed names."""

from __future__ import annotations

import re

import numpy as np
import pytest

from repro.core import build
from repro.core.config import ExecConfig
from repro.core.ops import OP_DELETE, OP_INSERT, OP_POINT, make_ops, plain_executor
from repro.core.trace import SPANS, span

SCOPES = {
    "fused": ["route", "delete_tiles", "fence_rows", "range_plumbing", "layout", "state_out"],
    "reference": ["route", "insert", "delete", "point", "successor", "range"],
}
PROGRAMS = {"fused": "jit_flix_apply_pallas", "reference": "jit__apply_ops_reference"}


@pytest.fixture(scope="module")
def compiled_text():
    """Each executor compiled for one tiny mixed batch (the fused one in
    interpret mode), as text."""
    keys = np.arange(0, 2048, 2, dtype=np.int32)
    state = build(keys, keys, node_size=8, nodes_per_bucket=4, fill=0.5)
    tag = np.array([OP_INSERT, OP_POINT, OP_DELETE, OP_POINT] * 4, np.int32)
    key = np.where(tag == OP_INSERT, np.arange(16) * 8 + 1, np.arange(16) * 8).astype(np.int32)
    ops, _ = make_ops(tag, key, key)
    texts = {}
    for impl in PROGRAMS:
        fn, args, kwargs = plain_executor(state, ops, impl=impl, cfg=ExecConfig(pipeline="on"))
        texts[impl] = fn.lower(*args, **kwargs).compile().as_text()
    return texts


@pytest.mark.parametrize(
    "impl, phase", [(impl, phase) for impl, phases in SCOPES.items() for phase in phases]
)
def test_every_phase_scope_reaches_the_compiled_program(compiled_text, impl, phase):
    scope = f"flix.{impl}.{phase}"
    names = re.findall(r'op_name="([^"]*)"', compiled_text[impl])
    assert any(f"/{scope}/" in n or n.endswith(f"/{scope}") for n in names), scope


@pytest.mark.parametrize("impl", PROGRAMS)
def test_the_program_keeps_its_name(compiled_text, impl):
    assert compiled_text[impl].startswith(f"HloModule {PROGRAMS[impl]},")


def test_span_names_are_fixed():
    assert len(set(SPANS)) == len(SPANS)
    with span("apply_ops_safe"):
        pass
    with pytest.raises(KeyError):
        span("not_a_span")
