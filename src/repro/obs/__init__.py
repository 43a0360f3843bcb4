"""Observability + autotuning for the RowClone engine (MEF x Levanter).

Three pieces:

* :mod:`repro.obs.metrics` — process-local counters/gauges/histograms
  with labeled series (stream, opcode, pool, tenant lane), plus the one
  sanctioned timing clock and the shared timer/percentile helpers every
  benchmark uses (rowlint RC105 enforces the monopoly).
* :mod:`repro.obs.trace` — named spans over the serving round and the
  flush lifecycle (``sched.step`` and its stages, ``flush -> drain``,
  ``ticket-wait``): ``jax.profiler`` trace sections always, wall-clock
  :class:`~repro.obs.trace.Span` records when recording is on;
  :class:`~repro.obs.trace.FlushTiming` rides on ``FlushTicket.timing``.
* :mod:`repro.obs.autotune` — per-backend
  :class:`~repro.obs.autotune.TunedProfile` (JSON under
  ``configs/tuned/``) written by ``benchmarks/bench_autotune.py`` and
  loaded by the engines at startup; explicit kwargs always win,
  missing profile means today's defaults.

This package imports nothing from ``repro.core``/``repro.kernels`` at
module scope (only lazily inside ``apply_profile``), so the core can
emit into it without an import cycle.
"""
from repro.obs.autotune import (TunedProfile, apply_profile, backend_key,
                                load_profile, pick_winner, profile_path,
                                save_profile, tuned_dir)
from repro.obs.metrics import (MetricsRegistry, Stopwatch, inc,
                               metrics_enabled, now, observe, percentile,
                               registry, set_gauge, set_metrics_enabled,
                               summarize, time_us)
from repro.obs.trace import (FlushTiming, Span, reset_spans, set_tracing,
                             span, span_tree, spans, tracing_enabled)

__all__ = [
    "MetricsRegistry",
    "registry",
    "inc",
    "set_gauge",
    "observe",
    "metrics_enabled",
    "set_metrics_enabled",
    "now",
    "Stopwatch",
    "time_us",
    "percentile",
    "summarize",
    "Span",
    "FlushTiming",
    "span",
    "spans",
    "reset_spans",
    "tracing_enabled",
    "set_tracing",
    "span_tree",
    "TunedProfile",
    "tuned_dir",
    "backend_key",
    "profile_path",
    "save_profile",
    "load_profile",
    "apply_profile",
    "pick_winner",
]
