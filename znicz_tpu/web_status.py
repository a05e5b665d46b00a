"""Web status: a live dashboard of running workflows.

Rebuilds the reference's ``veles/web_status.py`` + ``veles/web/``
(a Tornado UI where the master reported running workflows, slaves and
progress).  TPU-first deltas: there is no master–slave topology to
display — the cluster is an SPMD mesh — so the dashboard shows the
process's registered workflows: epoch/minibatch progress, best
metrics, device, mesh shape, per-unit timing.  Implementation is
stdlib ``http.server`` in a daemon thread (no tornado in this
environment): ``/`` is a self-refreshing HTML page, ``/status.json``
the machine-readable feed, ``/metrics`` the Prometheus text
exposition of the process-global :mod:`znicz_tpu.observe` registry
(compile counts, per-unit run-time histograms, transfer bytes,
serving latency — everything train + serve register; since round 16
one process typically hosts a FLEET, so ``/metrics`` aggregates N
serving/decode engines under per-engine labels plus the per-tenant
fleet series), and ``/trace.json`` a live Chrome-trace/Perfetto dump
of the host-span ring buffer (open it in ``ui.perfetto.dev``), and —
round 11 — ``/healthz`` (liveness, always 200) + ``/readyz``
(readiness fed from the registry: circuit-breaker state per engine,
serving queue age, last-step staleness; 503 while any ENGINE sheds
load — a fleet tenant's own breaker opening is NOT an engine outage:
it sheds exactly that tenant and is reported per tenant, never
flipping the process probe) so external supervisors can probe
training and every resident serving engine at once.  Round 18:
``/readyz`` on process 0 additionally folds per-process heartbeat
ages from ``znicz_heartbeat_age_seconds`` (aggregate pod health —
a stale peer makes the pod not ready past
``engine.ready_max_heartbeat_s``, unset = report-only).  Round 24:
``/flightrecord`` serves the ops flight recorder's journal
(``?since=<seq>&kind=<k1,k2>`` filters), and ``/readyz`` folds the
federation view — each :class:`~znicz_tpu.observe.federation.
Federator` source's scrape staleness, bounded by
``engine.ready_max_fed_age_s`` (unset = report-only).
"""

from __future__ import annotations

import html
import json
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from znicz_tpu.utils.logger import Logger


def gather_status(workflow) -> dict:
    """One workflow's live status snapshot (scalars only — safe to
    read from the serving thread while training runs).  Anything with
    a ``serving_status`` hook — a
    :class:`znicz_tpu.serving.ServingEngine`, a
    :class:`~znicz_tpu.serving.DecodeEngine`, or a whole
    :class:`~znicz_tpu.serving.FleetEngine` (per-tenant SLO state,
    models, replica groups) — reports its own snapshot through the
    same feed."""
    if hasattr(workflow, "serving_status"):
        return workflow.serving_status()
    from znicz_tpu.utils.introspect import (slowest_units,
                                            validation_metrics)
    out: dict = {"name": workflow.name,
                 "initialized": workflow.is_initialized,
                 "stopped": bool(workflow.stopped)}
    loader = getattr(workflow, "loader", None)
    if loader is not None and loader.is_initialized:
        out["epoch"] = int(loader.epoch_number)
        out["total_samples"] = int(loader.total_samples)
        schedule_len = len(loader._schedule)
        if schedule_len:
            out["epoch_progress_pt"] = round(
                100.0 * min(loader._cursor, schedule_len) / schedule_len,
                1)
    out.update(validation_metrics(workflow))
    decision = getattr(workflow, "decision", None)
    if decision is not None:
        out["complete"] = bool(getattr(decision, "complete", False))
    device = getattr(workflow, "device", None)
    if device is not None:
        out["backend"] = device.backend
        mesh = getattr(device, "mesh", None)
        if mesh is not None:
            out["mesh"] = {ax: int(n) for ax, n
                           in zip(mesh.axis_names, mesh.devices.shape)}
    out["slowest_units"] = slowest_units(workflow, n=5)
    return out


class WebStatusServer(Logger):
    """Serves ``/`` (HTML) and ``/status.json`` for every registered
    workflow.  ``port=0`` picks a free port (see :attr:`port`)."""

    def __init__(self, port: int = 0, host: str = "127.0.0.1") -> None:
        super().__init__()
        self._workflows: list = []
        self._lock = threading.Lock()
        self._started = time.time()
        status_server = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, fmt, *args):  # route into our logger
                status_server.debug("http: " + fmt, *args)

            def do_GET(self):
                if self.path.startswith("/status.json"):
                    body = json.dumps(status_server.status()).encode()
                    ctype = "application/json"
                elif self.path.startswith("/healthz"):
                    # liveness: the process answers — always 200
                    body = json.dumps(status_server.health()).encode()
                    self.send_response(200)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                elif self.path.startswith("/readyz"):
                    # readiness: fed from the observe registry (breaker
                    # state, queue age, last-step staleness) — 503
                    # tells an external supervisor to stop routing here
                    report = status_server.readiness()
                    body = json.dumps(report).encode()
                    self.send_response(200 if report["ready"] else 503)
                    self.send_header("Content-Type", "application/json")
                    self.send_header("Content-Length", str(len(body)))
                    self.end_headers()
                    self.wfile.write(body)
                    return
                elif self.path.startswith("/metrics"):
                    from znicz_tpu.observe import metrics
                    body = metrics.REGISTRY.to_prometheus().encode()
                    ctype = "text/plain; version=0.0.4; charset=utf-8"
                elif self.path.startswith("/trace.json"):
                    from znicz_tpu.observe import tracing
                    body = json.dumps(
                        tracing.TRACER.to_chrome_trace()).encode()
                    ctype = "application/json"
                elif self.path.startswith("/flightrecord"):
                    # round 24: the ops flight recorder's journal —
                    # ?since=<seq> and ?kind=<k1,k2> filter; newest
                    # 256 events by default so the page stays bounded
                    from urllib.parse import parse_qs, urlparse
                    from znicz_tpu.observe import recorder
                    q = parse_qs(urlparse(self.path).query)
                    since = int(q.get("since", ["0"])[0] or 0)
                    kinds = None
                    if q.get("kind"):
                        kinds = [k for k in
                                 q["kind"][0].split(",") if k]
                    rec = recorder.get_recorder()
                    if rec is None:
                        payload = {"events": [], "status": None}
                    else:
                        events = rec.dump_since(since, kinds=kinds)
                        payload = {"events": events[-256:],
                                   "status": rec.status()}
                    body = json.dumps(payload).encode()
                    ctype = "application/json"
                elif self.path == "/" or self.path.startswith("/index"):
                    body = status_server.render_html().encode()
                    ctype = "text/html; charset=utf-8"
                else:
                    self.send_response(404)
                    self.end_headers()
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

        # round-19 satellite: every /metrics endpoint exports
        # znicz_build_info (fleet debugging must tell which build a
        # scrape came from).  Fallback registration only — device
        # creation refreshes with platform/mesh/process labels; no
        # backend query here (a dashboard must not claim the chip).
        try:
            from znicz_tpu.observe import metrics as _metrics
            _metrics.set_build_info(fallback=True)
        except Exception:  # noqa: BLE001 — never block the dashboard
            pass
        self._httpd = ThreadingHTTPServer((host, port), Handler)
        self.host = host
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(
            target=self._httpd.serve_forever, name="web-status",
            daemon=True)
        self._thread.start()
        self.info("web status @ http://%s:%d/", self.host, self.port)

    # ------------------------------------------------------------------
    def register(self, workflow) -> None:
        with self._lock:
            if workflow not in self._workflows:
                self._workflows.append(workflow)

    def unregister(self, workflow) -> None:
        with self._lock:
            if workflow in self._workflows:
                self._workflows.remove(workflow)

    def status(self) -> dict:
        with self._lock:
            workflows = list(self._workflows)
        return {
            "uptime_s": round(time.time() - self._started, 1),
            "workflows": [gather_status(wf) for wf in workflows],
        }

    # -- supervisor probes (round 11) ----------------------------------
    def health(self) -> dict:
        """/healthz body: liveness only — the process is up and the
        status thread answers."""
        with self._lock:
            n = len(self._workflows)
        return {"status": "ok",
                "uptime_s": round(time.time() - self._started, 1),
                "workflows": n}

    def readiness(self) -> dict:
        """/readyz body, fed from the observe REGISTRY (so it reflects
        exactly what ``/metrics`` exports, not object state):

        - ``znicz_serving_breaker_state`` — any ENGINE with an OPEN
          breaker (2) makes the process not-ready (it is shedding
          every caller);
        - ``znicz_fleet_breaker_state`` (round 16) — per-TENANT fleet
          breakers are reported under ``tenants`` but are
          REPORT-ONLY: an open tenant breaker sheds exactly that
          tenant while every other tenant is served normally, so it
          must not flip a supervisor's routing decision;
        - ``znicz_serving_queue_age_seconds`` — reported per engine;
          not-ready when it exceeds ``engine.ready_max_queue_age_s``
          (default unset = report-only);
        - ``znicz_last_step_timestamp_seconds`` — per-workflow step
          staleness; not-ready when older than
          ``engine.ready_max_staleness_s`` (default unset =
          report-only, so a finished training run does not flip a
          serving process to 503);
        - ``znicz_model_version`` (round 13) — the live published
          model version per serving engine, reported so a supervisor
          can confirm which weights a replica is actually running;
        - ``znicz_snapshot_age_seconds`` (round 13) — time since each
          source (snapshotter prefix / publish directory) last wrote a
          GOOD artifact; not-ready when it exceeds
          ``engine.ready_max_snapshot_age_s`` (default unset =
          report-only), so a stalled trainer that stopped publishing
          shows up on the serving probe;
        - ``znicz_loader_rows_quarantined_total`` (round 19) — rows a
          quarantined shard delivered as zeros, per loader.
          REPORT-ONLY: quarantine-and-continue is degraded, not dead
          — restarting would lose more progress than the zeros cost.
        """
        from znicz_tpu.observe import metrics
        from znicz_tpu.utils.config import root
        now = time.time()
        out: dict = {"ready": True, "reasons": [],
                     "engines": {}, "workflows": {}}

        def not_ready(reason: str) -> None:
            out["ready"] = False
            out["reasons"].append(reason)

        fam = metrics.REGISTRY.get("znicz_serving_breaker_state")
        if fam is not None:
            for key, child in fam.items():
                (engine,) = key
                state = {0: "closed", 1: "half_open",
                         2: "open"}.get(int(child.value), "?")
                out["engines"].setdefault(engine, {})["breaker"] = state
                if state == "open":
                    not_ready(f"breaker open on engine {engine}")
        fam = metrics.REGISTRY.get("znicz_fleet_breaker_state")
        if fam is not None:
            out["tenants"] = {}
            for key, child in fam.items():
                fleet, tenant = key
                state = {0: "closed", 1: "half_open",
                         2: "open"}.get(int(child.value), "?")
                out["tenants"][f"{fleet}/{tenant}"] = state
        fam = metrics.REGISTRY.get("znicz_serving_queue_age_seconds")
        max_age = root.common.engine.get("ready_max_queue_age_s", None)
        if fam is not None:
            for key, child in fam.items():
                # ("engine",) pre-round-22 children, ("engine","pool")
                # after — /readyz watches the WORST pool per engine
                engine = key[0]
                age = round(float(child.value), 3)
                prior = out["engines"].setdefault(engine, {})
                age = max(age, prior.get("queue_age_s", 0.0))
                prior["queue_age_s"] = age
                if max_age is not None and age > float(max_age):
                    not_ready(f"queue age {age:.1f}s on engine "
                              f"{engine}")
        fam = metrics.REGISTRY.get("znicz_last_step_timestamp_seconds")
        max_stale = root.common.engine.get("ready_max_staleness_s", None)
        if fam is not None:
            for key, child in fam.items():
                (workflow,) = key
                stale = round(max(0.0, now - float(child.value)), 3)
                out["workflows"][workflow] = {"last_step_age_s": stale}
                if max_stale is not None and stale > float(max_stale):
                    not_ready(f"workflow {workflow} last step "
                              f"{stale:.0f}s ago")
        # round 18: aggregate pod health — per-process heartbeat ages
        # (fed by the coordinator-side HeartbeatMonitor from the
        # shared channel).  A stale peer makes the POD not ready when
        # engine.ready_max_heartbeat_s is set (unset = report-only:
        # single-host runs and gang supervisors that own restarts
        # themselves must not flip this process's probe).
        fam = metrics.REGISTRY.get("znicz_heartbeat_age_seconds")
        max_hb = root.common.engine.get("ready_max_heartbeat_s", None)
        if fam is not None:
            out["processes"] = {}
            for key, child in fam.items():
                (process,) = key
                age = float(child.value)
                out["processes"][process] = {
                    "heartbeat_age_s": (None if age == float("inf")
                                        else round(age, 3))}
                if max_hb is not None and age > float(max_hb):
                    not_ready(f"process {process} heartbeat "
                              f"{age:.0f}s stale")
        # round 19: silent data loss made loud — rows a quarantined
        # shard delivered as ZEROS.  REPORT-ONLY by design: a run that
        # chose quarantine-and-continue is degraded, not dead, and an
        # external supervisor restarting it would lose MORE progress;
        # the row count here (and on /metrics) is the operator signal.
        fam = metrics.REGISTRY.get("znicz_loader_rows_quarantined_total")
        if fam is not None:
            out["loaders"] = {}
            for key, child in fam.items():
                (loader,) = key
                out["loaders"][loader] = {
                    "rows_quarantined": int(child.value)}
        fam = metrics.REGISTRY.get("znicz_model_version")
        if fam is not None:
            for key, child in fam.items():
                (engine,) = key
                out["engines"].setdefault(engine, {})[
                    "model_version"] = int(child.value)
        fam = metrics.REGISTRY.get("znicz_snapshot_age_seconds")
        max_snap = root.common.engine.get("ready_max_snapshot_age_s",
                                          None)
        if fam is not None:
            out["artifacts"] = {}
            for key, child in fam.items():
                (source,) = key
                age = round(float(child.value), 3)
                out["artifacts"][source] = {"age_s": age}
                if max_snap is not None and age > float(max_snap):
                    not_ready(f"no good artifact from {source} for "
                              f"{age:.0f}s")
        # round 24: the federated view — when this process folds a
        # gang's children (supervisor/fleet/disagg federators), report
        # each source's scrape staleness; not-ready only when
        # engine.ready_max_fed_age_s is set AND a source is staler
        # (unset = report-only: a paused fold must not 503 a healthy
        # serving process)
        try:
            from znicz_tpu.observe import federation
            feds = federation.status()
        except Exception:  # noqa: BLE001 — probe must answer anyway
            feds = []
        if feds:
            out["federation"] = feds
            max_fed = root.common.engine.get("ready_max_fed_age_s",
                                             None)
            if max_fed is not None:
                worst = federation.max_age_s()
                if worst > float(max_fed):
                    not_ready(f"federated scrape {worst:.1f}s stale")
        return out

    # ------------------------------------------------------------------
    def render_html(self) -> str:
        status = self.status()
        rows = []
        for wf in status["workflows"]:
            metrics = {k: v for k, v in wf.items()
                       if k not in ("name", "slowest_units")}
            timing = "".join(
                f"<li>{html.escape(t['unit'])}: {t['total_s']}s / "
                f"{t['runs']}x</li>" for t in wf.get("slowest_units", []))
            rows.append(
                f"<div class='wf'><h2>{html.escape(wf['name'])}</h2>"
                f"<pre>{html.escape(json.dumps(metrics, indent=2))}"
                f"</pre><ul>{timing}</ul></div>")
        body = "\n".join(rows) or "<p>No workflows registered.</p>"
        return (
            "<!DOCTYPE html><html><head><meta charset='utf-8'>"
            "<meta http-equiv='refresh' content='2'>"
            "<title>znicz_tpu status</title>"
            "<style>body{font-family:monospace;margin:2em}"
            ".wf{border:1px solid #999;padding:1em;margin:1em 0}"
            "</style></head><body><h1>znicz_tpu</h1>"
            f"<p>uptime {status['uptime_s']}s</p>{body}</body></html>")

    def stop(self) -> None:
        self._httpd.shutdown()
        self._httpd.server_close()
        self._thread.join(timeout=10)
