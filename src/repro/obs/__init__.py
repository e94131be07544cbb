"""``repro.obs`` — unified observability: metrics registry, phase spans,
Chrome-trace export, live persist-waste gauges.

One process-wide default :class:`~repro.obs.registry.Registry` backs the
module-level helpers; instrumented modules cache metric objects at
import time (``_HIT = obs.counter("alloc.tcache_hit")``) so the hot-path
cost is one bound-method call with an enabled-flag branch — near zero
when disabled, tiny when enabled.

Metric naming conventions (see ROADMAP "Observability"):

  ``heap.*``      flush/fence/cas/drain counts of the live host heap
                  (registered as *sources* by ``PersistentHeap``)
  ``alloc.*``     host allocator paths (tcache, refill source, watermark)
  ``placement.*`` free-run index: exact-bucket vs overflow vs miss
  ``span.*``      large-span lease traffic (acquire/release/trim/free)
  ``device.*``    engine-side device-allocator call sites
  ``engine.*``    publish queue depth / flush batches
  ``sched.*``     admission (rejects, park-retries, queue depth)
  ``serve.*``     request latency (TTFT, total) histograms
  ``trie.*``      prefix-cache hit depth distribution
  ``recovery.*``  named recovery phases (span timings)
  ``persist.*``   waste gauges from an attached :class:`WasteMonitor`

``set_annotator(fn)`` has every span also enter ``fn(name)`` while the
registry is enabled: ``repro.serving`` puts the spans on the JAX
profiler's trace this way (``jax.profiler.TraceAnnotation``).
"""

from __future__ import annotations

from .registry import Counter, Gauge, Histogram, Registry, UnknownMetric
from .waste import WasteMonitor

__all__ = [
    "Counter", "Gauge", "Histogram", "Registry", "UnknownMetric",
    "WasteMonitor", "get_registry", "counter", "gauge", "gauge_fn",
    "histogram", "register_source", "span", "snapshot", "chrome_trace",
    "span_events", "set_annotator", "reset", "reset_all", "enable",
    "disable", "is_enabled", "attach_waste_monitor",
]

_default = Registry(enabled=True)


def get_registry() -> Registry:
    return _default


def counter(name: str) -> Counter:
    return _default.counter(name)


def gauge(name: str) -> Gauge:
    return _default.gauge(name)


def gauge_fn(name: str, fn) -> Gauge:
    return _default.gauge_fn(name, fn)


def histogram(name: str) -> Histogram:
    return _default.histogram(name)


def register_source(name: str, read, reset=None) -> None:
    _default.register_source(name, read, reset)


def span(name: str, **args):
    return _default.span(name, **args)


def snapshot() -> dict:
    return _default.snapshot()


def chrome_trace() -> dict:
    return _default.chrome_trace()


def span_events(since: float = float("-inf")):
    return _default.span_events(since)


def set_annotator(fn) -> None:
    """Enter ``fn(name)`` (a context manager) around every span of the
    default registry while it is enabled; ``None`` removes it."""
    _default.annotator = fn


def reset(*names: str) -> None:
    _default.reset(*names)


def reset_all() -> None:
    _default.reset_all()


def enable() -> None:
    _default.enable()


def disable() -> None:
    _default.disable()


def is_enabled() -> bool:
    return _default.enabled


def attach_waste_monitor(mem, registry: Registry | None = None
                         ) -> WasteMonitor:
    """Attach a :class:`WasteMonitor` to ``mem``'s tracer slot and bind
    its waste gauges (``persist.redundant_flushes`` / ``.empty_fences``)
    into the registry.  Returns the monitor; detach with
    ``mem.tracer = None``."""
    mon = WasteMonitor(registry or _default)
    mem.tracer = mon
    return mon
