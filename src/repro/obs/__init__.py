"""Unified telemetry: structured metrics, host-side event tracing with
Perfetto export (and profiler annotations), structured logging, and the
run manifest.

Quick start::

    from repro import obs

    tracer = obs.enable_tracing()           # off by default — see trace.py
    ... run ...
    tracer.export("trace.json")             # manifest stamped automatically

    reg = obs.default_registry()
    reg.absorb("serve.pool", pool.stats())  # legacy dict -> canonical names
    print(reg.snapshot())

The contract (zero cost when off, host-only recording, namespace scheme)
is DESIGN.md §11.
"""
from repro.obs import trace
from repro.obs.log import get_logger
from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    default_registry,
    reset_default,
)
from repro.obs.sinks import run_manifest
from repro.obs.trace import Tracer, disable as disable_tracing, enable as enable_tracing
from repro.obs.validate import validate_manifest, validate_trace

__all__ = [
    "trace",
    "get_logger",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "default_registry",
    "reset_default",
    "run_manifest",
    "Tracer",
    "enable_tracing",
    "disable_tracing",
    "validate_manifest",
    "validate_trace",
]


def configure(trace_path=None, capacity: int = 1 << 16):
    """Convenience switch used by launch entry points: enable tracing when
    a ``--trace PATH`` was given, returning (tracer, path) — tracer is the
    disabled singleton when path is None."""
    if trace_path is None:
        return trace.get(), None
    return trace.enable(capacity=capacity), trace_path
