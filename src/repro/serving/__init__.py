"""Public serving surface (see ``docs/api.md`` for the full contract).

The supported entry point is :func:`load_engine` — it sniffs artifact
vs bundle sources and picks the paged / fixed-slot / speculative engine.
``submit()`` on any engine returns a :class:`RequestHandle`.  Everything
in ``__all__`` is covered by the API-stability tests in
``tests/test_api.py``; anything else is internal and may change without
a deprecation cycle.
"""
from repro.kernels.dispatch import attach_dispatch_hook  # noqa: F401
from repro.serving.engine import (FixedSlotEngine, Request,  # noqa: F401
                                  ServeEngine, make_engine)
from repro.serving.handle import RequestHandle  # noqa: F401
from repro.serving.http import AsyncServer  # noqa: F401
from repro.serving.kv_cache import (PageAllocator, PagedKVCache,  # noqa: F401
                                    PageError)
from repro.serving.loader import load_engine  # noqa: F401
from repro.serving.obs import (NULL_RECORDER, MetricsRegistry,  # noqa: F401
                               NullRecorder, Recorder, SloThresholds,
                               SloTracker, Tracer, log, slo_report,
                               summary_table, validate_chrome_trace,
                               validate_prometheus)
from repro.serving.prefix import RadixPrefixIndex  # noqa: F401
from repro.serving.quality import QualityProbe  # noqa: F401
from repro.serving.sampling import SamplingParams  # noqa: F401
from repro.serving.scheduler import Scheduler, StepPlan  # noqa: F401
from repro.serving.speculative import SpeculativeEngine  # noqa: F401

__all__ = [
    # factory + per-request handle (the supported front door)
    "load_engine",
    "RequestHandle",
    "AsyncServer",
    # engines (constructors are public; prefer load_engine)
    "ServeEngine",
    "FixedSlotEngine",
    "SpeculativeEngine",
    # request/sampling types
    "Request",
    "SamplingParams",
    # paged KV + prefix reuse
    "PagedKVCache",
    "PageAllocator",
    "PageError",
    "RadixPrefixIndex",
    "Scheduler",
    "StepPlan",
    # observability
    "Recorder",
    "NullRecorder",
    "NULL_RECORDER",
    "MetricsRegistry",
    "Tracer",
    "log",
    "summary_table",
    "validate_prometheus",
    "validate_chrome_trace",
    # deep observability (PR 10)
    "QualityProbe",
    "attach_dispatch_hook",
    "SloTracker",
    "SloThresholds",
    "slo_report",
    # deprecated (one release; use load_engine)
    "make_engine",
]
