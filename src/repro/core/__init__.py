"""FliX core: the paper's flipped-indexing CDS as a composable JAX module."""

from repro.core.state import (
    EMPTY,
    KEY_DTYPE,
    MAX_VALID,
    MIN_KEY,
    NOT_FOUND,
    VAL_DTYPE,
    FliXState,
    empty_state,
)
from repro.core.batch import (
    bucket_of,
    bucket_slices,
    dedup_last_wins,
    gather_kv_sublists,
    gather_sublists,
    sort_batch,
)
from repro.core.build import build, build_from_sorted, plan_geometry
from repro.core.config import ExecConfig, TileTable
from repro.core.query import (
    dense_range_scan,
    point_query,
    range_query,
    successor_query,
    with_successor_cache,
)
from repro.core.insert import insert, insert_safe, insert_with_slices
from repro.core.delete import delete, merge_underfull
from repro.core.expiry import NO_EXPIRY, attach_expiry, bucket_min_exp, expire_state
from repro.core.ops import (
    DEFAULT_MAX_RESULTS,
    OP_DELETE,
    OP_EXPIRE,
    OP_INSERT,
    OP_NOP,
    OP_POINT,
    OP_RANGE,
    OP_SUCCESSOR,
    OpBatch,
    apply_ops,
    apply_ops_safe,
    make_ops,
    touched_buckets,
    unsort,
)
from repro.core.invariants import (
    check_invariants,
    check_range_results,
    check_tiered_invariants,
)
from repro.core.residency import TieredFliX, bucket_device_bytes
from repro.core.restructure import (
    restructure,
    restructure_auto,
    restructure_grow,
    restructure_shrink,
)
