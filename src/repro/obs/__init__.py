"""repro.obs — unified observability for every modeled-time subsystem.

The lens the repro's attribution claims are argued through:

    trace   — ``Tracer``: typed span/instant/counter events on the
              modeled clock, recorded into a bounded ring buffer
              ("flight recorder", O(1) append); ``NULL_TRACER`` is the
              zero-cost disabled default every subsystem falls back to
    metrics — ``MetricsRegistry``: hierarchical counter/gauge registry;
              the legacy per-subsystem ``stats()`` dicts are thin
              adapters over it
    export  — Chrome/Perfetto ``trace_event`` JSON export (tracks =
              tenants, engines, links, pool) and the per-link
              utilization / queueing-delay report that decomposes a
              run's modeled seconds by fabric tier
    console — the one sanctioned stdout channel for ``src/repro`` CLI
              drivers (bare ``print(`` is linted out of the library)

Quickstart::

    from repro.obs import Tracer, write_chrome_trace, link_report

    tr = Tracer()
    tx = Transport(topology, tracer=tr)
    eng = Engine.local(model, cfg, transport=tx, route=r, tracer=tr)
    run_trace(eng, trace)
    tx.quiesce()
    write_chrome_trace(tr, "run.json")          # open in ui.perfetto.dev
    print(format_link_report(link_report(tx)))  # modeled-seconds by link
"""

from repro.obs.export import (format_link_report, link_report,
                              link_report_from_trace, link_tier,
                              tier_report, to_chrome_trace,
                              validate_trace_events, write_chrome_trace)
from repro.obs.metrics import Counter, Gauge, MetricsRegistry, write_json
from repro.obs.trace import (CAT_ARBITER, CAT_ENGINE, CAT_FABRIC, CAT_KV,
                             CAT_LINK, CAT_REQUEST, CAT_SCHED, NULL_TRACER,
                             Event, JsonlSink, NullTracer, Tracer,
                             events_from_jsonl, resolve,
                             rotated_jsonl_paths)

__all__ = [
    "CAT_ARBITER", "CAT_ENGINE", "CAT_FABRIC", "CAT_KV", "CAT_LINK",
    "CAT_REQUEST", "CAT_SCHED", "Counter", "Event", "Gauge", "JsonlSink",
    "MetricsRegistry", "NULL_TRACER", "NullTracer", "Tracer",
    "events_from_jsonl", "format_link_report", "link_report",
    "link_report_from_trace", "link_tier", "resolve",
    "rotated_jsonl_paths", "tier_report",
    "to_chrome_trace", "validate_trace_events", "write_chrome_trace",
    "write_json",
]
