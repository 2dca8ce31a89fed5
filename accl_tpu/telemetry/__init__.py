"""accl-tpu telemetry: tracing and metrics across every executor.

Observability lives next to the data plane (the ACCL posture: hardware
performance counters and per-call duration registers the host reads back
after the fact) and one schema threads through every layer:

  - the NATIVE trace ring (runtime.cpp record_span, ACCL_RT_TRACE=1)
    records per-call spans — opcode, bytes, start/end ns, retcode,
    deferred-mismatch detail, sequencer-counter deltas — drained through
    ctypes (EmuRank.trace_read) and lifted into events by
    telemetry.native;
  - the HOST tracer (telemetry.tracer) collects facade call spans and
    the fused-sequence record -> lint -> compile -> dispatch phases,
    every span carrying its timing.predict estimate where one exists;
  - telemetry.export renders Chrome trace-event JSON (one track per
    rank/executor, Perfetto-loadable) and the predicted-vs-measured
    residual table, validated against EVENT_SCHEMA (jsonschema);
  - telemetry.feedback closes the loop: measured spans ->
    timing.calibrate samples -> refit LinkParams -> ACCL.autotune.

On top of the post-hoc trace rides the ALWAYS-ON observability layer
(metrics.py / recorder.py), fed at span-emission time through the
tracer's observer seam — never at trace drain:

  - the streaming metrics registry: counters/gauges/bounded
    streaming-quantile histograms keyed by (op, algorithm, protocol,
    world), Prometheus text exposition + a JSON snapshot embedded in
    every exported trace's meta;
  - the drift sentinel: rolling predicted-vs-measured residual bands
    per op with a band-leave verdict and per-rank straggler
    attribution (the sensing half of always-on autotuning);
  - the flight recorder: last-N spans per track, frozen into a
    self-contained post-mortem on any sticky nonzero retcode
    (errors.notify_sticky_retcode) without tracing ever having been
    enabled.

Entry points: bench.py --trace emits the full trace + residual section
and bench.py --obs-gate proves the sentinel + overhead claims;
tools/accl_trace.py exports/validates/selftests standalone (--metrics
replays a trace through the registry). Host tracing is off by default
(ACCL_TELEMETRY=1 or telemetry.enable()); the observability layer is
ON by default (ACCL_OBS=0 opts out) and rides the same emission seam.
The fully-disabled path is one predicate per site, gated <1% on the
bench smoke path. See docs/observability.md for the schema table and
the calibration-loop walkthrough.
"""

import os as _os

from .tracer import (  # noqa: F401
    DEFAULT_CAPACITY,
    SCHEMA_VERSION,
    Tracer,
    disable,
    enable,
    get_tracer,
)
from .export import (  # noqa: F401
    EVENT_SCHEMA,
    WIRE_FAULT_KEYS,
    residual_rows,
    residual_summary,
    to_chrome,
    validate_trace,
    wire_health_report,
    write_trace,
)
from .feedback import (  # noqa: F401
    autotune_from_trace,
    calibrate_compute_from_trace,
    calibrate_from_trace,
    calibrate_tiers_from_trace,
    default_compute_fit,
    default_link,
    default_tier_links,
    residual_improvement,
    residual_report,
)
from . import native  # noqa: F401
from . import metrics  # noqa: F401
from . import recorder  # noqa: F401
from .metrics import (  # noqa: F401
    DriftSentinel,
    MetricsRegistry,
    get_registry,
    get_sentinel,
    replay_trace,
)
from .recorder import (  # noqa: F401
    FlightRecorder,
    get_recorder,
    last_error_trace,
)


def enable_observability() -> None:
    """Arm the always-on layer: install the process-wide metrics
    observer and flight recorder on the process tracer. Spans go live
    (the emission seam feeds them) but the trace ring still only
    collects under ACCL_TELEMETRY/enable()."""
    metrics.install(get_tracer())
    recorder.install(get_tracer())


def disable_observability() -> None:
    """Detach metrics + flight recorder (the 'nobody watching' state
    the <1% disabled-overhead gate measures)."""
    metrics.uninstall(get_tracer())
    recorder.uninstall(get_tracer())


def observability_enabled() -> bool:
    return recorder.armed()


# always-on by default: the metrics registry and flight recorder are
# bounded and cost ~a dict hit + deque append per span, so they ride
# every process unless explicitly opted out
if _os.environ.get("ACCL_OBS", "1") not in ("", "0", "false", "off"):
    enable_observability()
