"""repro.obs — flight recorder + metrics plane for the OCTOPUS pipeline.

Opt-in tracing of every uplink from encode dispatch to codebook merge:

    from repro import obs

    with obs.recording("trace.jsonl"):
        client.round(batch)            # every layer logs to the trace

    with obs.span("cohort/pull"):      # octopus/cohort/pull on the
        host = np.asarray(x)           # jax.profiler timeline

    with obs.dispatch_monitor() as counts:
        client.round(batch)
    assert (counts.encoder_passes, counts.encode_dispatches) == (1, 1)

Default is a no-op: ``obs.active()`` returns None and instrumented call
sites skip all event work. Setting ``$OCTOPUS_TRACE=<path>`` before the
process imports ``repro.obs`` installs a recorder automatically (how CI
traces the unmodified examples). Summaries: ``python -m repro.obs.report
trace.jsonl``. See ``recorder.py`` for the event schema and the §2.5
metadata-only capture rule.
"""
from .metrics import (Counter, DispatchCounts, Gauge, MetricsRegistry,
                      dispatch_monitor)
from .recorder import (ENV_VAR, EVENT_KINDS, PAYLOAD_META_FIELDS,
                       SPAN_PREFIX, FlightRecorder, active, install,
                       install_from_env, payload_meta, recording, span,
                       uninstall)

__all__ = [
    "Counter",
    "DispatchCounts",
    "ENV_VAR",
    "EVENT_KINDS",
    "FlightRecorder",
    "Gauge",
    "MetricsRegistry",
    "PAYLOAD_META_FIELDS",
    "SPAN_PREFIX",
    "active",
    "dispatch_monitor",
    "install",
    "install_from_env",
    "payload_meta",
    "recording",
    "span",
    "uninstall",
]

install_from_env()
