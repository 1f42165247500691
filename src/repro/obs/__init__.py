# repro.obs — production numerics observability.
#
# Three pillars, one import:
#   registry - unified typed metrics (counters/gauges/histograms with labels,
#              Prometheus text exposition + JSON snapshot); every scattered
#              stats() dict in serving/launch/dispatch is a view over it
#   monitor  - live calibration-envelope monitoring per GEMM site through the
#              dispatch trace-hook seam: inside / near-edge / violated, with
#              overflow counting and pluggable alert sinks
#   spans    - lightweight trace spans (serving request lifecycle, train
#              steps, AOT compiles) exporting Chrome-trace/Perfetto JSON;
#              scoped spans and hot-path phases also land in a running
#              jax.profiler trace
#
# ``registry``/``spans`` import eagerly (stdlib-only at import, safe from
# the lowest layers — core.dispatch mirrors its plan-cache stats here; a
# phase resolves jax.profiler on first use). ``monitor`` and
# ``export`` resolve lazily: monitor pulls in jax + dispatch, and eager
# loading would cycle through core.dispatch's own import of this package.
from .registry import (Counter, Gauge, Histogram, MetricError, Registry,
                       default_registry)
from .spans import (Span, SpanRecorder, current_span, phase, recorder, span,
                    start_span)

_LAZY = {
    "monitor": ".monitor", "export": ".export",
    "NumericsMonitor": ".monitor", "monitoring": ".monitor",
    "SiteStats": ".monitor", "cfg_capacity": ".monitor",
    "INSIDE": ".monitor", "NEAR_EDGE": ".monitor", "VIOLATED": ".monitor",
    "UNMONITORED": ".monitor", "STATUS_CODE": ".monitor",
    "chrome_trace": ".export", "save_chrome_trace": ".export",
    "start_metrics_server": ".export",
}

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricError", "Registry",
    "default_registry",
    "Span", "SpanRecorder", "current_span", "phase", "recorder", "span",
    "start_span",
    *sorted(set(_LAZY) - {"monitor", "export"}),
]


def __getattr__(name: str):
    mod = _LAZY.get(name)
    if mod is None:
        raise AttributeError(f"module 'repro.obs' has no attribute {name!r}")
    import importlib
    module = importlib.import_module(mod, __name__)
    if name in ("monitor", "export"):
        return module
    return getattr(module, name)
