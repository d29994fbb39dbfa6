"""Observability: metrics, tracing, timing, artifacts.

This package is the single place the repo records *what actually ran*:
which backend served each ``(op, regularization)`` dispatch, at what shapes,
which programs were built and what their trace, lower and compile cost,
how long the host regions (train steps, serve calls) took, and how long
benchmarked calls took.  It exists because the paper's headline claim is
performance (O(n log n) soft sorting/ranking, "an order of magnitude
faster" — Blondel et al., 2020) and an unverifiable claim is not a
reproduction.

Modules
-------
``repro.obs.metrics``
    Process-local counters and histograms, keyed by name + labels.  Gated
    by ``REPRO_METRICS`` (any value but ``0``/``false``/``off`` enables;
    default enabled).  When disabled every recording call is a constant-time
    no-op and no state is retained.
``repro.obs.tracing``
    ``jax.named_scope`` wrappers so dispatched backend kernels are
    attributable in jaxprs, HLO and ``jax.profiler`` traces; ``span``, the
    one host-side region (profiler annotation + per-thread parent stack +
    ``span_s`` histogram); and the build listener on ``jax.monitoring``
    that records each program's trace, lower and compile as ``build_s``
    (installed here, once per process).
``repro.obs.timing``
    Wall-clock timing helpers (``block_until_ready`` walls, median
    us/call, nearest-rank percentiles) shared by ``benchmarks/`` and
    ``repro.launch.serve``.
``repro.obs.artifacts``
    The one structured-JSON ``BENCH_*.json`` emitter + schema validator
    used by ``benchmarks/run.py``, ``repro.launch.train`` and
    ``repro.launch.serve`` (schema ``repro.bench/v1``; see
    docs/BENCHMARKS.md).  ``python -m repro.obs FILE...`` validates
    artifacts and is what CI gates the bench smoke on.

Layering: ``repro.obs`` imports only jax/stdlib — never ``repro.core`` or
``repro.kernels`` — so the dispatch layer can depend on it without cycles.
"""

from repro.obs import artifacts, metrics, timing, tracing
from repro.obs.artifacts import (
    SCHEMA_VERSION,
    bench_payload,
    collect_meta,
    validate_bench_payload,
    write_bench_artifact,
)
from repro.obs.metrics import (
    counter_inc,
    counters,
    enabled,
    histograms,
    observe,
    reset,
    set_enabled,
    snapshot,
)
from repro.obs.timing import time_fn, timed
from repro.obs.tracing import backend_scope, scope_name, span

tracing.install_build_listener()

__all__ = [
    "SCHEMA_VERSION",
    "artifacts",
    "backend_scope",
    "bench_payload",
    "collect_meta",
    "counter_inc",
    "counters",
    "enabled",
    "histograms",
    "metrics",
    "observe",
    "reset",
    "scope_name",
    "set_enabled",
    "snapshot",
    "span",
    "time_fn",
    "timed",
    "timing",
    "tracing",
    "validate_bench_payload",
    "write_bench_artifact",
]
