"""Serving observability: span tracing, metrics, time attribution.

* :mod:`repro.obs.tracer` — low-overhead thread-aware span tracer
  (``Tracer``): spans are profiler ``TraceMe`` events whenever a
  profiler session is active, so they share the device trace's clock,
  and exact per-name self-time aggregates while enabled;
* :mod:`repro.obs.metrics` — typed metrics registry (counters, gauges,
  log-bucketed latency histograms) + the ``StatsView`` legacy facade;
* :mod:`repro.obs.report` — per-stage wall-clock attribution
  (``stage_breakdown``) of the host's time to stage dispatch and host
  buckets;
* :mod:`repro.obs.energy` — modeled joules/token accounting
  (``EnergyAccountant``): loop-aware HLO cost analysis of each compiled
  engine stage priced with the paper's TALU per-MAC PDP row plus a
  documented DRAM pJ/byte constant, multiplied by live per-stage
  invocation counters.
"""
from .energy import EnergyAccountant, StageEnergy, format_energy
from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      StatsView)
from .report import format_breakdown, stage_breakdown
from .tracer import Span, Tracer

__all__ = ["Counter", "EnergyAccountant", "Gauge", "Histogram",
           "MetricsRegistry", "StageEnergy", "StatsView", "Span",
           "Tracer", "format_breakdown", "format_energy",
           "stage_breakdown"]
