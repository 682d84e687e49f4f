"""bluefog_tpu.telemetry — cross-rank metrics, counters, and event journal.

What `timeline.py` (chrome-trace spans) does not provide: always-on,
lock-light counters / gauges / fixed-bucket histograms plus a per-rank JSONL event journal, threaded
through the gossip hot paths (islands win ops, shm mailbox, tcp
transport) and the failure paths (resilience detector / healing /
degraded steps).

Enable with ``BFTPU_TELEMETRY=1`` (or ``=<dir>`` to choose where
per-rank snapshot + journal files land; default ``/tmp/bftpu_telemetry``).
When the variable is unset, ``get_registry()`` returns a shared
``NullRegistry`` whose metric handles are no-ops — instrumented call
sites cost one attribute load and a falsy branch.

Merge per-rank snapshots with ``python -m bluefog_tpu.telemetry`` (JSON
and Prometheus text exposition), or programmatically via
:func:`merge_snapshots` / :func:`merge_job_snapshots`.  See
docs/OBSERVABILITY.md.

Stdlib-only: importable without jax, numpy, or the native library.
"""

from bluefog_tpu.telemetry.registry import (
    DEFAULT_LATENCY_BUCKETS_S,
    SERVE_LATENCY_BUCKETS_S,
    LEDGER_COLLECTED,
    LEDGER_DEPOSITS,
    LEDGER_DRAINED,
    LEDGER_PENDING,
    SNAPSHOT_SCHEMA,
    Counter,
    Gauge,
    Histogram,
    NullRegistry,
    Registry,
    add_op_listener,
    get_registry,
    journal_max_bytes,
    journal_paths,
    note_op,
    read_journal,
    remove_op_listener,
    reset,
    telemetry_dir,
)
from bluefog_tpu.telemetry.merge import (
    MERGED_SCHEMA,
    find_snapshots,
    ledger_balance,
    load_snapshot,
    merge_job_snapshots,
    merge_snapshots,
    to_prometheus,
)

__all__ = [
    "SNAPSHOT_SCHEMA",
    "MERGED_SCHEMA",
    "DEFAULT_LATENCY_BUCKETS_S",
    "SERVE_LATENCY_BUCKETS_S",
    "LEDGER_DEPOSITS",
    "LEDGER_COLLECTED",
    "LEDGER_DRAINED",
    "LEDGER_PENDING",
    "Counter",
    "Gauge",
    "Histogram",
    "Registry",
    "NullRegistry",
    "get_registry",
    "reset",
    "telemetry_dir",
    "read_journal",
    "journal_paths",
    "journal_max_bytes",
    "note_op",
    "add_op_listener",
    "remove_op_listener",
    "find_snapshots",
    "load_snapshot",
    "merge_snapshots",
    "merge_job_snapshots",
    "ledger_balance",
    "to_prometheus",
]
