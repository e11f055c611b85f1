"""Stdlib-only Prometheus ``/metrics`` endpoint + per-host heartbeats.

:class:`MetricsServer` serves the metric registry (obs/registry.py) in the
Prometheus text exposition format from a daemon-thread
``ThreadingHTTPServer`` — no prometheus_client dependency, nothing on the
training hot path (the scrape reads whatever the loop last published).
Both the training loop (``train.py --metrics-port``) and the serving driver
(``inference/serve.py --metrics-port``) mount one.

:class:`HeartbeatThread` closes the pod-scale blind spot: a wedged or
straggling host today is invisible until a collective times out (up to
``--peer-timeout-seconds`` later). Each host publishes ``(wall clock,
step)`` through the jax.distributed KV store (ft/multihost.py — the same
host-side gRPC channel the fault fence uses, so no device collectives), and
every host exports per-peer gauges:

    ftl_host_heartbeat_age_seconds{host="3"}  — staleness; alert on > 2-3x
                                                 the publish interval
    ftl_host_heartbeat_step{host="3"}         — per-host step; a flat or
                                                 lagging host is a straggler

so the straggler is visible on ANY surviving host's scrape before the
collective deadline fires.
"""

import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable, Optional

from .registry import MetricRegistry, default_registry


class MetricsServer:
    """``GET /metrics`` → registry render; ``GET /healthz`` → ok."""

    def __init__(self, registry: Optional[MetricRegistry] = None,
                 host: str = "0.0.0.0", port: int = 0):
        self.registry = registry or default_registry()
        self._host = host
        self._requested_port = port
        self.port: Optional[int] = None
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    def start(self) -> int:
        registry = self.registry

        class _Handler(BaseHTTPRequestHandler):
            def do_GET(self):  # noqa: N802 (http.server API)
                if self.path.split("?")[0] in ("/metrics", "/"):
                    body = registry.render().encode()
                    self.send_response(200)
                    self.send_header("Content-Type",
                                     "text/plain; version=0.0.4; "
                                     "charset=utf-8")
                elif self.path == "/healthz":
                    body = b"ok\n"
                    self.send_response(200)
                    self.send_header("Content-Type", "text/plain")
                else:
                    body = b"not found\n"
                    self.send_response(404)
                    self.send_header("Content-Type", "text/plain")
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):  # scrapes must not spam the
                pass                       # audit-trail stdout

        self._httpd = ThreadingHTTPServer((self._host, self._requested_port),
                                          _Handler)
        self._httpd.daemon_threads = True
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        name="ftl-metrics", daemon=True)
        self._thread.start()
        return self.port

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None
        self._thread = None


class HeartbeatThread(threading.Thread):
    """Publish this host's heartbeat and export every peer's as gauges.

    ``step_fn`` returns the current training step (read without locking —
    an int read is atomic in CPython and staleness of one tick is fine).
    Single-process runs degrade to a self-heartbeat (age ~0), so the gauge
    surface is identical on a laptop and a pod.
    """

    def __init__(self, step_fn: Callable[[], int],
                 registry: Optional[MetricRegistry] = None,
                 interval_seconds: float = 10.0,
                 clock: Callable[[], float] = time.time):
        super().__init__(name="ftl-heartbeat", daemon=True)
        self.step_fn = step_fn
        self.registry = registry or default_registry()
        self.interval = interval_seconds
        self.clock = clock
        self._stop_event = threading.Event()
        self._age = self.registry.gauge(
            "ftl_host_heartbeat_age_seconds",
            "Seconds since each host last published a heartbeat")
        self._step = self.registry.gauge(
            "ftl_host_heartbeat_step",
            "Last training step each host reported in its heartbeat")

    def beat_once(self) -> None:
        """One publish + one peer sweep (also the test entry point)."""
        from ..ft import multihost

        multihost.publish_heartbeat(int(self.step_fn()))
        now = self.clock()
        for host, (t, step) in multihost.read_heartbeats().items():
            self._age.labels(host=str(host)).set(max(0.0, now - t))
            self._step.labels(host=str(host)).set(step)

    def run(self) -> None:
        while not self._stop_event.is_set():
            try:
                self.beat_once()
            except Exception:
                pass  # observability must never take down training
            self._stop_event.wait(self.interval)

    def stop(self) -> None:
        """Stop and join: on a pod the beat is a KV-client call, and a
        daemon thread still inside native code when the interpreter
        finalizes aborts the process (see data/prefetch.py ``close``).
        Bounded — a wedged KV channel must not hang the exit."""
        self._stop_event.set()
        if self.is_alive():
            self.join(timeout=5.0)
