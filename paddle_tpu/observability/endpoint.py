"""Live introspection endpoint: ``/metrics`` + ``/debug/requests``.

A stdlib-only (``http.server``) HTTP surface over the observability layer —
the production-metrics idiom of the vLLM/SGLang serving lineage (scrape a
``/metrics`` Prometheus page, curl a debug page when a request is slow)
without adding any dependency:

- ``GET /metrics``        Prometheus text exposition concatenated across
                          every attached ``MetricsRegistry`` (the process-
                          wide default registry is always included first —
                          compile tracking, train stalls — then e.g. each
                          scheduler's ServingMetrics registry).
- ``GET /debug/requests`` JSON from every attached debug source — for a
                          scheduler: the live request table (state, phase,
                          tokens, slot, preemptions, age), recent completed
                          traces, the stall breakdown, SLO accounting, and
                          the flight-recorder ring (``?last=N`` trims it).
- ``GET /debug/replicas`` JSON fleet view from every attached
                          ``ServingRouter`` (``add_router``): per-replica
                          health/breaker/generation/load + prefix-cache
                          stats, supervisor reap/restart accounting, and
                          the router's failover counters.
- ``GET /debug/programs`` JSON compiled-program inventory: every
                          executable the process compiled (train steps,
                          static functions, serving decode/prefill
                          buckets) with its argument signature and XLA
                          cost analysis — FLOPs, bytes accessed, peak
                          temp memory, buffer/donation sizes.
                          ``?analyze=0`` skips cost analysis (listing
                          only, never compiles).
- ``GET /debug/memory``   JSON device-memory census: owner-tagged live
                          bytes and watermarks from the process-default
                          ``DeviceMemoryLedger`` plus every attached
                          scheduler's ledger, including any retained
                          OOM-forensics report.
- ``GET /debug/stepprofile`` JSON latest in-step profile per attached
                          scheduler: named-region device-time shares from
                          the last ``capture_step_profile`` run plus the
                          zero-sync in-program telemetry snapshot. Read-
                          only — scraping never starts a device trace.
- ``GET /debug``          JSON index of every debug route above.
- ``GET /healthz``        truthful health: the worst state across every
                          attached health source, as a plain-text body —
                          ``ok`` / ``degraded`` (shed ladder engaged) /
                          ``draining`` with HTTP 200 (the process IS
                          alive), ``dead`` with 503 when a scheduler's
                          driver thread has exited with work pending (or
                          a health source itself raises). With no sources
                          attached it stays a bare liveness 200 "ok".

The server runs on a daemon thread (``ThreadingHTTPServer``), binds
``127.0.0.1`` and an ephemeral port by default, and never touches the
device: every handler reads host-side state the scheduler already keeps, so
a scrape cannot stall a decode step.

Typical use::

    ep = ObservabilityEndpoint()
    ep.add_scheduler(sched)          # registry + debug_state in one call
    host, port = ep.start()
    ... requests serve ...           # curl http://host:port/metrics
    ep.stop()
"""

from __future__ import annotations

import json
import threading
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Dict, List, Optional, Tuple
from urllib.parse import parse_qs, urlparse

from paddle_tpu.observability.metrics import MetricsRegistry, get_registry

__all__ = ["ObservabilityEndpoint"]


class ObservabilityEndpoint:
    """One process's scrape + debug HTTP surface."""

    def __init__(self, registries: Optional[List[MetricsRegistry]] = None,
                 host: str = "127.0.0.1", port: int = 0,
                 include_default_registry: bool = True):
        self._registries: List[MetricsRegistry] = []
        if include_default_registry:
            self._registries.append(get_registry())
        for r in registries or ():
            self.add_registry(r)
        self._debug_sources: "Dict[str, Callable[[], dict]]" = {}
        self._health_sources: "Dict[str, Callable[[], dict]]" = {}
        self._replica_sources: "Dict[str, Callable[[], dict]]" = {}
        self._memory_sources: "Dict[str, Callable[[], dict]]" = {}
        self._timelines: Dict[str, object] = {}     # MetricsTimeline
        self._postmortems: Dict[str, object] = {}   # PostmortemStore
        self._stepprofile_sources: "Dict[str, Callable[[], dict]]" = {}
        self._host = host
        self._port = int(port)
        self._server: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    # --------------------------------------------------------- attachment
    def add_registry(self, registry: MetricsRegistry):
        if registry not in self._registries:
            self._registries.append(registry)

    def add_debug_source(self, name: str, fn: Callable[[], dict]):
        """``fn()`` -> JSON-able dict, rendered under ``name`` in
        ``/debug/requests``."""
        self._debug_sources[str(name)] = fn

    def add_health_source(self, name: str, fn: Callable[[], dict]):
        """``fn()`` -> dict with a ``"state"`` key in
        ``ok|degraded|draining|dead``; ``/healthz`` reports the worst state
        across all sources. A source that raises counts as ``dead``."""
        self._health_sources[str(name)] = fn

    def add_memory_source(self, name: str, fn: Callable[[], dict]):
        """``fn()`` -> a ``DeviceMemoryLedger.census_report()``-shaped
        dict, rendered under ``name`` in ``/debug/memory``."""
        self._memory_sources[str(name)] = fn

    def add_timeline(self, name: str, timeline):
        """Attach a ``MetricsTimeline``; queryable under ``name`` via
        ``/debug/timeline?metric=...&last=N&tier=...``."""
        self._timelines[str(name)] = timeline

    def add_postmortem(self, name: str, store):
        """Attach a ``PostmortemStore``; ``/debug/postmortem`` captures an
        on-demand bundle from it and returns everything retained."""
        self._postmortems[str(name)] = store

    def add_stepprofile_source(self, name: str, fn: Callable[[], dict]):
        """``fn()`` -> a ``step_profile_state()``-shaped dict (latest
        named-region capture + telemetry), rendered under ``name`` in
        ``/debug/stepprofile``. Must never touch the device."""
        self._stepprofile_sources[str(name)] = fn

    def add_scheduler(self, scheduler, name: Optional[str] = None):
        """Attach a ContinuousBatchingScheduler: its metrics registry feeds
        ``/metrics``, ``debug_state()`` feeds ``/debug/requests``,
        ``health()`` feeds ``/healthz``, (when device observability is
        on) its ledger census feeds ``/debug/memory``, its timeline /
        postmortem stores feed ``/debug/timeline`` + ``/debug/postmortem``,
        and ``step_profile_state()`` feeds ``/debug/stepprofile``."""
        self.add_registry(scheduler.metrics.registry)
        key = name or f"scheduler{len(self._debug_sources)}"
        self.add_debug_source(key, scheduler.debug_state)
        if hasattr(scheduler, "health"):
            self.add_health_source(key, scheduler.health)
        ledger = getattr(scheduler, "device_ledger", None)
        if ledger is not None:
            self.add_memory_source(key, ledger.census_report)
        if getattr(scheduler, "timeline", None) is not None:
            self.add_timeline(key, scheduler.timeline)
        if getattr(scheduler, "postmortems", None) is not None:
            self.add_postmortem(key, scheduler.postmortems)
        if hasattr(scheduler, "step_profile_state"):
            self.add_stepprofile_source(key, scheduler.step_profile_state)
        return self

    def add_router(self, router, name: Optional[str] = None):
        """Attach a ``ServingRouter``: its router-level registry (fault
        counters + per-replica labeled gauges) plus every replica
        scheduler's registry feed ``/metrics``, its fleet ``health()``
        feeds ``/healthz``, ``debug_state()`` feeds both
        ``/debug/requests`` and the dedicated ``/debug/replicas`` page,
        and its fleet timeline / postmortem stores feed
        ``/debug/timeline`` + ``/debug/postmortem``."""
        self.add_registry(router.metrics.registry)
        for rep in router.replicas:
            self.add_registry(rep.sched.metrics.registry)
        key = name or f"router{len(self._replica_sources)}"
        self.add_debug_source(key, router.debug_state)
        self.add_health_source(key, router.health)
        self._replica_sources[key] = router.debug_state
        if getattr(router, "timeline", None) is not None:
            self.add_timeline(key, router.timeline)
        if getattr(router, "postmortems", None) is not None:
            self.add_postmortem(key, router.postmortems)
        return self

    # ------------------------------------------------------------ content
    def metrics_text(self) -> str:
        return "".join(r.prometheus_text() for r in self._registries)

    def debug_requests(self, last: Optional[int] = None) -> dict:
        out = {}
        for name, fn in self._debug_sources.items():
            try:
                state = fn()
            except Exception as e:  # a broken source must not 500 the page
                state = {"error": f"{type(e).__name__}: {e}"}
            if last and isinstance(state, dict):
                fr = state.get("flight_recorder")
                if isinstance(fr, list):
                    state = dict(state, flight_recorder=fr[-last:])
            out[name] = state
        return out

    def debug_replicas(self) -> dict:
        """The ``/debug/replicas`` payload: per-router replica tables
        (health, breaker, generation, load, prefix-cache stats) and
        supervisor/failover accounting."""
        out = {}
        for name, fn in self._replica_sources.items():
            try:
                out[name] = fn()
            except Exception as e:  # a broken source must not 500 the page
                out[name] = {"error": f"{type(e).__name__}: {e}"}
        return out

    def debug_programs(self, analyze: bool = True) -> dict:
        """The ``/debug/programs`` payload: the process-wide compiled-
        program inventory with XLA cost analysis (FLOPs / bytes accessed /
        peak temp memory / buffer+donation sizes) per executable."""
        from paddle_tpu.observability.program_inventory import (
            get_program_inventory,
        )

        return get_program_inventory().snapshot(analyze=analyze)

    def debug_memory(self) -> dict:
        """The ``/debug/memory`` payload: owner-tagged device-byte census
        from the process-default ledger (train-side owners) plus every
        attached scheduler's ledger."""
        from paddle_tpu.observability.device_memory import get_device_ledger

        out = {"default": get_device_ledger().census_report()}
        for name, fn in self._memory_sources.items():
            try:
                out[name] = fn()
            except Exception as e:  # a broken source must not 500 the page
                out[name] = {"error": f"{type(e).__name__}: {e}"}
        return out

    def debug_timeline(self, metric: Optional[str] = None,
                       last: Optional[int] = None,
                       tier: str = "raw") -> dict:
        """The ``/debug/timeline`` payload. Without ``metric``: per-store
        tier summaries + available metric names. With ``metric``: the
        ``[(t, value)]`` series from every attached timeline that has it."""
        out = {}
        for name, tl in self._timelines.items():
            try:
                if metric is None:
                    out[name] = {"summary": tl.snapshot(),
                                 "metrics": tl.metric_names()}
                else:
                    out[name] = {"metric": metric, "tier": tier,
                                 "points": tl.query(metric, last=last,
                                                    tier=tier)}
            except Exception as e:  # a broken source must not 500 the page
                out[name] = {"error": f"{type(e).__name__}: {e}"}
        return out

    def debug_stepprofile(self) -> dict:
        """The ``/debug/stepprofile`` payload: each attached scheduler's
        latest named-region capture summary + telemetry snapshot. Read-
        only host state — a scrape NEVER triggers a capture (captures run
        a device trace; start them from ``capture_step_profile``)."""
        out = {}
        for name, fn in self._stepprofile_sources.items():
            try:
                out[name] = fn()
            except Exception as e:  # a broken source must not 500 the page
                out[name] = {"error": f"{type(e).__name__}: {e}"}
        return out

    def debug_postmortem(self, capture: bool = True) -> dict:
        """The ``/debug/postmortem`` payload: optionally freeze one
        on-demand bundle per attached store (default), then return every
        retained bundle — the mid-incident "give me everything" curl."""
        out = {}
        for name, store in self._postmortems.items():
            try:
                if capture:
                    store.capture("on_demand", "requested via "
                                  "/debug/postmortem", force=True)
                out[name] = {"summary": store.summary(),
                             "bundles": store.bundles()}
            except Exception as e:  # a broken source must not 500 the page
                out[name] = {"error": f"{type(e).__name__}: {e}"}
        return out

    DEBUG_ROUTES = {
        "/metrics": "Prometheus text exposition across attached registries",
        "/debug": "this index",
        "/debug/requests": "live request tables, traces, stall breakdown, "
                           "flight recorder (?last=N)",
        "/debug/replicas": "per-router replica fleet view",
        "/debug/programs": "compiled-program inventory with XLA cost "
                           "analysis (?analyze=0 to skip analysis)",
        "/debug/memory": "owner-tagged device-memory census + OOM "
                         "forensics",
        "/debug/timeline": "metrics time-series history "
                           "(?metric=NAME&last=N&tier=raw|10s|60s; no "
                           "metric lists names + retention)",
        "/debug/postmortem": "correlated incident bundles; captures an "
                             "on-demand bundle first (?capture=0 to only "
                             "list)",
        "/debug/stepprofile": "latest named-region step-profile capture + "
                              "in-program telemetry (read-only; never "
                              "triggers a capture)",
        "/healthz": "worst health state across attached sources",
    }

    def debug_index(self) -> dict:
        """The ``/debug`` payload: every registered route with a one-line
        description, so the debug surface is discoverable from a curl."""
        return {"routes": dict(self.DEBUG_ROUTES)}

    _HEALTH_ORDER = ("ok", "degraded", "draining", "dead")

    def health(self) -> Tuple[int, str]:
        """Aggregate ``(http_code, body)`` for ``/healthz``: the worst
        state any source reports. ``dead`` is the only non-200 — degraded
        and draining processes are still alive and still serving (a k8s
        liveness probe must not kill a box for shedding load)."""
        worst = 0
        for fn in self._health_sources.values():
            try:
                state = str(fn().get("state", "ok"))
            except Exception:
                state = "dead"       # a health source that can't answer
                                     # IS the failure it exists to report
            if state not in self._HEALTH_ORDER:
                state = "dead"
            worst = max(worst, self._HEALTH_ORDER.index(state))
        body = self._HEALTH_ORDER[worst]
        return (503 if body == "dead" else 200), body

    # ---------------------------------------------------------- lifecycle
    def start(self) -> Tuple[str, int]:
        if self._server is not None:
            return self.address
        ep = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):  # silence per-request stderr lines
                pass

            def _send(self, code: int, body: str, ctype: str):
                data = body.encode()
                self.send_response(code)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(data)))
                self.end_headers()
                self.wfile.write(data)

            def do_GET(self):
                url = urlparse(self.path)
                if url.path == "/metrics":
                    self._send(200, ep.metrics_text(),
                               "text/plain; version=0.0.4")
                elif url.path == "/debug/requests":
                    q = parse_qs(url.query)
                    last = None
                    if "last" in q:
                        try:
                            last = int(q["last"][0])
                        except ValueError:
                            pass
                    body = json.dumps(ep.debug_requests(last=last),
                                      default=str, indent=2)
                    self._send(200, body, "application/json")
                elif url.path == "/debug/replicas":
                    body = json.dumps(ep.debug_replicas(),
                                      default=str, indent=2)
                    self._send(200, body, "application/json")
                elif url.path == "/debug/programs":
                    q = parse_qs(url.query)
                    analyze = q.get("analyze", ["1"])[0] not in ("0",
                                                                 "false")
                    body = json.dumps(ep.debug_programs(analyze=analyze),
                                      default=str, indent=2)
                    self._send(200, body, "application/json")
                elif url.path == "/debug/memory":
                    body = json.dumps(ep.debug_memory(),
                                      default=str, indent=2)
                    self._send(200, body, "application/json")
                elif url.path == "/debug/timeline":
                    q = parse_qs(url.query)
                    metric = q.get("metric", [None])[0]
                    tier = q.get("tier", ["raw"])[0]
                    last = None
                    if "last" in q:
                        try:
                            last = int(q["last"][0])
                        except ValueError:
                            pass
                    body = json.dumps(
                        ep.debug_timeline(metric=metric, last=last,
                                          tier=tier),
                        default=str, indent=2)
                    self._send(200, body, "application/json")
                elif url.path == "/debug/stepprofile":
                    body = json.dumps(ep.debug_stepprofile(),
                                      default=str, indent=2)
                    self._send(200, body, "application/json")
                elif url.path == "/debug/postmortem":
                    q = parse_qs(url.query)
                    capture = q.get("capture", ["1"])[0] not in ("0",
                                                                 "false")
                    body = json.dumps(ep.debug_postmortem(capture=capture),
                                      default=str, indent=2)
                    self._send(200, body, "application/json")
                elif url.path in ("/debug", "/debug/"):
                    body = json.dumps(ep.debug_index(),
                                      default=str, indent=2)
                    self._send(200, body, "application/json")
                elif url.path == "/healthz":
                    code, body = ep.health()
                    self._send(code, body, "text/plain")
                else:
                    self._send(404, json.dumps(
                        {"error": "not found",
                         "routes": sorted(ep.DEBUG_ROUTES)}),
                        "application/json")

        self._server = ThreadingHTTPServer((self._host, self._port), Handler)
        self._server.daemon_threads = True
        self._thread = threading.Thread(target=self._server.serve_forever,
                                        name="obs-endpoint", daemon=True)
        self._thread.start()
        return self.address

    @property
    def address(self) -> Tuple[str, int]:
        if self._server is None:
            return (self._host, self._port)
        host, port = self._server.server_address[:2]
        return (host, port)

    @property
    def url(self) -> str:
        host, port = self.address
        return f"http://{host}:{port}"

    def stop(self):
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
            if self._thread is not None:
                self._thread.join(timeout=5)
                self._thread = None

    def __enter__(self):
        self.start()
        return self

    def __exit__(self, *exc):
        self.stop()
        return False
