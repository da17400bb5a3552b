"""jimm_tpu.obs — unified observability: one registry, spans, goodput.

Public surface::

    from jimm_tpu import obs

    reg = obs.get_registry("jimm_train")        # namespaced registry
    reg.counter("steps_total").inc()
    with obs.span("checkpoint_save"): ...        # host timing + TraceAnnotation
    acct = obs.GoodputAccounter()
    with acct.measure("data_wait"): batch = next(it)
    watch = obs.CompileWatch()                   # every compile request, named
    obs.snapshot()                               # unified {prefix_name: value}
    obs.render_prometheus()                      # one text dump, all namespaces

Disable all optional instrumentation with ``JIMM_OBS=0`` (or
``obs.set_enabled(False)``): spans and goodput measures become no-ops;
registries keep counting (serve counters are product behavior).
"""

from jimm_tpu.obs.compiles import CompileWatch
from jimm_tpu.obs.exporters import (JsonlExporter, console_table,
                                    diff_snapshots, parse_prometheus_text,
                                    render_prometheus_text)
from jimm_tpu.obs.goodput import BUCKETS, GoodputAccounter
from jimm_tpu.obs.journal import (EventJournal, chain, configure_journal,
                                  correlate, current_cid, get_journal,
                                  new_correlation_id, read_events,
                                  reset_journal)
from jimm_tpu.obs.prof import (CaptureManager, MemoryMonitor,
                               configure_capture, get_capture_manager,
                               maybe_trigger, reset_capture)
from jimm_tpu.obs.registry import (Counter, DuplicateMetricError, Gauge,
                                   Histogram, MetricRegistry, enabled,
                                   get_registry, percentile, publish,
                                   registries, render_prometheus,
                                   set_enabled, snapshot, unpublish)
from jimm_tpu.obs.slo import SloEngine, SloObjective
from jimm_tpu.obs.spans import new_trace_id, span
from jimm_tpu.obs.timeline import (export_timeline, validate_chrome_trace,
                                   write_timeline)

__all__ = [
    "BUCKETS", "CaptureManager", "CompileWatch", "Counter",
    "DuplicateMetricError", "EventJournal", "Gauge", "GoodputAccounter",
    "Histogram", "JsonlExporter", "MemoryMonitor", "MetricRegistry",
    "SloEngine", "SloObjective", "chain", "configure_capture",
    "configure_journal", "console_table", "correlate", "current_cid",
    "diff_snapshots", "enabled", "export_timeline", "get_capture_manager",
    "get_journal", "get_registry", "maybe_trigger",
    "new_correlation_id", "new_trace_id", "parse_prometheus_text",
    "percentile", "publish", "read_events", "registries",
    "render_prometheus", "render_prometheus_text", "reset_capture",
    "reset_journal", "set_enabled", "snapshot", "span",
    "unpublish", "validate_chrome_trace", "write_timeline",
]
