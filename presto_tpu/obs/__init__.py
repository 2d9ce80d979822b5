"""presto_tpu.obs — unified tracing, metrics, and flight recorder.

The cross-cutting observability layer: one metrics registry
(obs/metrics.py), one structured tracer (obs/trace.py), one flight
recorder (obs/flightrec.py), and the JAX compile/device telemetry
helpers (obs/jaxtel.py), bundled by :class:`Observability` so every
subsystem threads a single handle instead of five dialects of ad-hoc
accounting.

Cost contract: everything is off-by-default-cheap.  A disabled
Observability answers every record call with one branch, and a survey
run without observability is byte-identical to an uninstrumented one
(no telemetry files are ever written while disabled).

Enabling it:

  * the serve layer is always observed (a resident service without
    /metrics is blind) — `SearchService` builds an enabled handle;
  * batch surveys opt in via ``SurveyConfig.obs`` (an ObsConfig or an
    Observability) or process-wide with ``PRESTO_TPU_OBS=1``.

See docs/OBSERVABILITY.md for the metric catalog, span taxonomy, and
flight-recorder triage guide.
"""

from __future__ import annotations

import os
import threading
from dataclasses import dataclass
from typing import Optional

from presto_tpu.obs.metrics import MetricsRegistry
from presto_tpu.obs.flightrec import FlightRecorder, find_dumps
from presto_tpu.obs.trace import (NOOP_SPAN, SpanContext, Tracer,
                                  chrome_trace, write_chrome_trace)

__all__ = [
    "ObsConfig", "Observability", "get_obs", "configure",
    "resolve_obs", "maybe_span", "MetricsRegistry", "Tracer", "SpanContext",
    "FlightRecorder", "find_dumps", "chrome_trace",
    "write_chrome_trace", "NOOP_SPAN",
]

#: environment switch: PRESTO_TPU_OBS=1 enables the process default
ENV_SWITCH = "PRESTO_TPU_OBS"


@dataclass
class ObsConfig:
    """Observability knobs (wire-safe: plain values only)."""
    enabled: bool = False
    #: directory for spans.jsonl + trace.perfetto.json; None defers to
    #: the survey workdir (flush(default_dir=...)) or disables export
    trace_dir: Optional[str] = None
    #: flight-recorder ring capacity (records)
    flightrec_capacity: int = 2048
    #: logical service name stamped on dumps/reports
    service: str = "presto_tpu"

    @classmethod
    def from_env(cls) -> "ObsConfig":
        on = os.environ.get(ENV_SWITCH, "") not in ("", "0")
        return cls(enabled=on,
                   trace_dir=os.environ.get(ENV_SWITCH + "_DIR")
                   or None)


class Observability:
    """One handle bundling registry + tracer + flight recorder."""

    def __init__(self, cfg: Optional[ObsConfig] = None,
                 registry: Optional[MetricsRegistry] = None):
        self.cfg = cfg or ObsConfig()
        self.enabled = bool(self.cfg.enabled)
        self.metrics = registry if registry is not None else \
            MetricsRegistry(enabled=self.enabled)
        self.flightrec = FlightRecorder(
            capacity=self.cfg.flightrec_capacity,
            enabled=self.enabled)
        jsonl = (os.path.join(self.cfg.trace_dir, "spans.jsonl")
                 if self.cfg.trace_dir else None)
        self.tracer = Tracer(enabled=self.enabled, jsonl_path=jsonl,
                             on_finish=self.flightrec.note_span)

    # -- convenience fronts -------------------------------------------
    def span(self, name: str, parent=None, **attrs):
        """Start a span (no-op singleton when disabled)."""
        if not self.enabled:
            return NOOP_SPAN
        return self.tracer.span(name, parent=parent, **attrs)

    def event(self, kind: str, **fields) -> None:
        """Record a discrete event into the flight recorder."""
        if not self.enabled:
            return
        self.flightrec.add(kind, **fields)

    def dump_flight(self, workdir: str, reason: str) -> Optional[str]:
        """Post-mortem: dump ring + open spans + metrics snapshot.
        Never raises."""
        if not self.enabled:
            return None
        try:
            path = self.flightrec.dump(
                workdir, reason,
                open_spans=self.tracer.open_spans(),
                metrics=self.metrics.snapshot())
        except Exception:
            return None
        if path is not None:
            self.metrics.counter(
                "flightrec_dumps_total",
                "Flight-recorder post-mortem dumps",
                ("reason",)).labels(reason=reason).inc()
        return path

    def flush(self, default_dir: Optional[str] = None) -> None:
        """Export buffered spans as a Perfetto/Chrome trace into
        cfg.trace_dir (or `default_dir`).  Safe to call repeatedly;
        never raises."""
        if not self.enabled:
            return
        d = self.cfg.trace_dir or default_dir
        if not d:
            return
        try:
            os.makedirs(d, exist_ok=True)
            spans = self.tracer.finished()
            if spans:
                write_chrome_trace(
                    os.path.join(d, "trace.perfetto.json"), spans)
                if self.tracer._jsonl_path is None:
                    # no streaming sink configured: snapshot the span
                    # buffer so presto-report still has spans.jsonl
                    import json as _json
                    from presto_tpu.io.atomic import atomic_write_text
                    atomic_write_text(
                        os.path.join(d, "spans.jsonl"),
                        "".join(_json.dumps(s.to_json(),
                                            sort_keys=True) + "\n"
                                for s in spans))
            # kernel-cost book -> kernel_costs.json (the roofline
            # section presto-report renders); no-op when nothing was
            # harvested, never runs device work
            from presto_tpu.obs import costmodel
            costmodel.write_costs(self, d)
        except Exception:
            pass


# ----------------------------------------------------------------------
# process-wide default handle
# ----------------------------------------------------------------------

_default: Optional[Observability] = None
_default_lock = threading.Lock()


def get_obs() -> Observability:
    """The process default Observability (enabled iff
    PRESTO_TPU_OBS=1 at first use, or after configure())."""
    global _default
    if _default is None:
        with _default_lock:
            if _default is None:
                _default = Observability(ObsConfig.from_env())
    return _default


def configure(cfg: ObsConfig) -> Observability:
    """Replace the process default (tests, app entry points)."""
    global _default
    with _default_lock:
        _default = Observability(cfg)
    return _default


def maybe_span(obs: Optional[Observability], name: str, **attrs):
    """``obs.span(name, **attrs)`` at a call site that may have been
    handed no handle: the no-op span for ``obs=None``."""
    if obs is None:
        return NOOP_SPAN
    return obs.span(name, **attrs)


def resolve_obs(obj) -> Observability:
    """Normalize a SurveyConfig-style ``obs`` field: None -> the
    process default, ObsConfig -> a fresh handle, Observability ->
    itself."""
    if obj is None:
        return get_obs()
    if isinstance(obj, Observability):
        return obj
    if isinstance(obj, ObsConfig):
        return Observability(obj)
    raise TypeError("obs must be ObsConfig or Observability, not %r"
                    % type(obj).__name__)
