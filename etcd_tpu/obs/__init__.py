"""Sampled proposal-lifecycle tracing for the batched hosting path.

PR 4's telemetry plane answers *what happened* (counters, invariant
sweep, flight recorder); this package answers *where the time went*:
deterministically sampled proposals are stamped with monotonic clocks
at every pipeline stage — propose-enqueue, round staging, device
dispatch, Ready extraction, WAL fsync, outbound send, commit, apply —
on every member that touches them, keyed by ``(group, term, index)`` so
peer-side spans need no wire-format change (Dapper's causal join trick:
the identifiers already on the wire ARE the trace id).

Pieces:

* ``tracer.Tracer`` — lock-cheap per-member span collector with a
  bounded ring (drops are counted on pkg.metrics, never silent).
* ``spans`` — the always-on round-span recorder: every host phase of
  a member round and of an engine call with wall and thread-CPU time,
  parent and ``(member, round)``, in a bounded ring per thread and as a
  ``TraceAnnotation`` of any open profiler session (so the spans lie
  beside the device ops on one clock); the program's phase timers and
  the tracer's stage/dispatch/extract stamps are set from them.
* ``export`` — Chrome-trace / Perfetto JSON exporter + validator.
* ``tools/trace_merge.py`` — joins per-member dumps into one timeline
  with cross-process clock-offset estimation from send/recv pairs.
* ``fleet`` — the fleet observatory (ISSUE 10): layout of the
  device-side group-state SummaryFrame plus the host FleetHub
  (``etcd_tpu_fleet_*`` families, groups×time heatmap ring, counted
  anomaly flags); ``tools/fleet_console.py`` renders a live cluster.
* ``artifacts`` — the one collision-free ``artifacts/`` naming scheme
  every observability dump (flightrec/tracering/fleetheat) shares.

Tracing is OFF by default and purely host-side: the jitted round
program and protocol state are bit-identical with it on or off
(tests/obs/test_tracing.py pins both). The fleet summary is likewise
OFF by default; it IS device-side, but a pure read — bit-parity is
pinned the same way (tests/batched/test_fleet.py).
"""

from .tracer import STAGES, Tracer, make_tracer  # noqa: F401
from .export import chrome_trace, validate_chrome_trace  # noqa: F401
from .fleet import FleetHub, FleetLayout  # noqa: F401
from .artifacts import dump_path  # noqa: F401
