"""The engine's host spans, written into the profiler's trace.

``span(name)`` is a ``jax.profiler.TraceAnnotation`` named ``flix:<name>``.
It shares the device trace's clock, so a reader of the trace can tell which
step of the host driver the device waited on.  Off a trace it costs what a
``TraceAnnotation`` costs (under a microsecond); it reads no flag.

The spans, each where its work happens:

  * ``make_ops`` — the batch's transfers, sort and inverse permutation;
  * ``apply_ops_safe`` — the whole host driver call;
  * ``sync.has_updates`` — ``resolve_impl``'s blocking read of the tags,
    only when the caller does not say whether the batch updates;
  * ``dispatch.fused`` / ``dispatch.reference`` — the executor call;
  * ``sync.needs_restructure`` — ``apply_ops_safe``'s blocking read of the
    overflow flag, which waits for the executor;
  * ``restructure`` — regrowing the table and replaying the batch.

``core/distributed.shard_apply_ops_safe`` writes its own around the same
steps, and reuses ``sync.has_updates``, ``sync.needs_restructure`` and
``restructure``:

  * ``shard_apply_ops_safe`` — the whole sharded host call;
  * ``sync.has_ranges`` — ``shard_apply_ops``' blocking read of the tags,
    only when the caller does not say whether the batch holds a RANGE;
  * ``dispatch.sharded`` — the sharded executor call.

The device side is named by ``jax.named_scope``: ``flix.fused.*`` in the
fused executor's wrapper (``kernels/flix_apply``), ``flix.reference.*``
in the reference executor (``core/ops``) and ``flix.shard.*`` in the
sharded executors' bodies (``core/distributed``), around the inner ones.
"""

from __future__ import annotations

import jax

PREFIX = "flix:"
SPANS = (
    "make_ops",
    "apply_ops_safe",
    "sync.has_updates",
    "sync.needs_restructure",
    "dispatch.fused",
    "dispatch.reference",
    "restructure",
    "shard_apply_ops_safe",
    "sync.has_ranges",
    "dispatch.sharded",
)
_NAMES = {name: PREFIX + name for name in SPANS}


def span(name: str) -> jax.profiler.TraceAnnotation:
    """The span ``flix:<name>``; ``name`` is one of ``SPANS``."""
    return jax.profiler.TraceAnnotation(_NAMES[name])
