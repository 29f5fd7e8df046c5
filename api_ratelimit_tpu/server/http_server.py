"""HTTP listeners: main port (/json + /healthcheck) and the debug port.

Main port mirrors src/server/server_impl.go:
  - POST /json: jsonpb <-> proto translation of the v3 RPC with status
    mapping OK->200, OVER_LIMIT->429, UNKNOWN/error->500, bad request->400
    (server_impl.go:62-104).
  - GET /healthcheck (server_impl.go:213).

Debug port (DEBUG_PORT=6070) mirrors server_impl.go:217-250:
  - GET /            endpoint index
  - GET /stats       current stat values (expvar equivalent)
  - GET /rlconfig    running config dump (runner.go:108-113)
  - GET /debug/pprof/        thread stack dump (goroutine-profile analog)
  - GET /debug/pprof/profile?seconds=N&hz=F  on-demand CPU profile: an
    all-thread statistical sampler in collapsed-stack format (loadable by
    flamegraph.pl / speedscope / pprof's collapsed importer)
  - GET /debug/pprof/heap[?top=N]  tracemalloc heap snapshot. Arming is an
    explicit opt-in: ?start=1 begins tracing, a later plain GET returns the
    snapshot, ?stop=1 disarms; a bare GET never changes state

Both are stdlib ThreadingHTTPServer instances with SO_REUSEPORT, matching
the reference's go_reuseport listeners (server_impl.go:115,131,141).
"""

from __future__ import annotations

import json
import logging
import socket
import socketserver
import sys
import threading
import time
import traceback
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Callable

from google.protobuf import json_format

from ..backends.overload import OverloadError
from ..limiter.cache import CacheError, DeadlineExceededError
from ..pb import rls_v3
from ..service.ratelimit import RateLimitService, ServiceError
from .. import tracing
from ..utils.deadline import deadline_scope
from . import proto_adapter
from .health import HealthChecker

logger = logging.getLogger("ratelimit.server.http")


class _ReusePortHTTPServer(ThreadingHTTPServer):
    daemon_threads = True
    allow_reuse_address = True

    def server_bind(self):
        if hasattr(socket, "SO_REUSEPORT"):
            try:
                self.socket.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEPORT, 1)
            except OSError:
                pass
        socketserver.TCPServer.server_bind(self)


class _Handler(BaseHTTPRequestHandler):
    protocol_version = "HTTP/1.1"
    routes_get: dict[str, Callable[["_Handler"], None]] = {}
    routes_post: dict[str, Callable[["_Handler"], None]] = {}

    def log_message(self, format, *args):  # noqa: A002 (stdlib signature)
        logger.debug("http: " + format, *args)

    def _write(self, status: int, body: bytes, content_type: str = "text/plain"):
        self.send_response(status)
        self.send_header("Content-Type", content_type)
        self.send_header("Content-Length", str(len(body)))
        self.end_headers()
        self.wfile.write(body)

    def do_GET(self):  # noqa: N802
        path = self.path.split("?", 1)[0]
        handler = self.routes_get.get(path)
        if handler is None and path.startswith("/debug/pprof"):
            handler = self.routes_get.get("/debug/pprof/")
        if handler is None:
            self._write(404, b"404 page not found\n")
            return
        handler(self)

    def do_POST(self):  # noqa: N802
        path = self.path.split("?", 1)[0]
        handler = self.routes_post.get(path)
        if handler is None:
            self._write(404, b"404 page not found\n")
            return
        handler(self)


def _make_handler_class(name: str) -> type[_Handler]:
    return type(name, (_Handler,), {"routes_get": {}, "routes_post": {}})


class HttpServer:
    """One listener + its route table; serve() runs in the caller's thread,
    serve_background() in a daemon thread."""

    def __init__(self, host: str, port: int, name: str):
        self._handler_cls = _make_handler_class(f"{name}Handler")
        self._server = _ReusePortHTTPServer((host, port), self._handler_cls)
        self._thread: threading.Thread | None = None
        self.name = name

    @property
    def port(self) -> int:
        return self._server.server_address[1]

    def add_get(self, path: str, fn: Callable[[_Handler], None]) -> None:
        self._handler_cls.routes_get[path] = fn

    def add_post(self, path: str, fn: Callable[[_Handler], None]) -> None:
        self._handler_cls.routes_post[path] = fn

    def endpoints(self) -> list[str]:
        return sorted(
            set(self._handler_cls.routes_get) | set(self._handler_cls.routes_post)
        )

    def serve(self) -> None:
        self._server.serve_forever(poll_interval=0.1)

    def serve_background(self) -> None:
        self._thread = threading.Thread(
            target=self.serve, name=f"http-{self.name}", daemon=True
        )
        self._thread.start()

    def shutdown(self) -> None:
        self._server.shutdown()
        self._server.server_close()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
            self._thread = None


def add_json_handler(
    server: HttpServer,
    service: RateLimitService,
    stats_scope=None,
    deadline_propagation: bool = True,
) -> None:
    """POST /json — HTTP/JSON mirror of the v3 RPC (server_impl.go:62-104).
    stats_scope (optional) records transport.json_ms: handler wall time —
    body read + jsonpb conversion + the service call; the same block is
    the ratelimit.service.transport.json profiler span.

    deadline_propagation reads Envoy's x-envoy-expected-rq-timeout-ms
    request header (the HTTP twin of the gRPC deadline) and binds it via
    utils/deadline.py, so expired work sheds with 504 instead of answering
    late."""
    h_receive = (
        stats_scope.scope("transport").histogram("json_ms")
        if stats_scope is not None
        else None
    )

    def _remaining_seconds(h: _Handler) -> float | None:
        if not deadline_propagation:
            return None
        raw = h.headers.get("x-envoy-expected-rq-timeout-ms")
        if not raw:
            return None
        try:
            return float(raw) / 1e3
        except ValueError:
            return None  # junk header: no deadline, not a 400

    def handle(h: _Handler) -> None:
        # HTTP middleware span honoring inbound B3 headers
        # (src/tracing/lightstep.go:107-160); no-op when tracing is off.
        with tracing.host_span("ratelimit.service.transport.json"):
            t0 = time.perf_counter() if h_receive is not None else 0.0
            with tracing.start_http_server_span("/json", h.headers) as span:
                with tracing.activate(span):
                    with deadline_scope(_remaining_seconds(h)):
                        _handle_json(h)
            if h_receive is not None:
                h_receive.record((time.perf_counter() - t0) * 1e3)

    def _handle_json(h: _Handler) -> None:
        # A malformed Content-Length must be a 400, not a ValueError that
        # drops the connection; a negative one must not turn into an
        # unbounded rfile.read.
        try:
            length = int(h.headers.get("Content-Length", 0))
        except (TypeError, ValueError):
            h._write(400, b"Bad Request: invalid Content-Length\n")
            return
        body = h.rfile.read(length) if length > 0 else b""
        if not body:
            h._write(400, b"Bad Request: empty body\n")
            return
        try:
            req = json_format.Parse(body, rls_v3.RateLimitRequest())
        except json_format.ParseError as e:
            h._write(400, f"Bad Request: {e}\n".encode())
            return
        try:
            internal = proto_adapter.request_from_v3(req)
            overall, statuses, headers = service.should_rate_limit(internal)
            resp = proto_adapter.response_to_v3(overall, statuses, headers)
        except DeadlineExceededError as e:
            # the caller's propagated deadline passed: a late 200 helps
            # nobody — 504, matching the gRPC DEADLINE_EXCEEDED mapping
            h._write(504, f"Gateway Timeout: {e}\n".encode())
            return
        except OverloadError as e:
            # shed by admission control (unavailable posture): retriable
            h._write(503, f"Service Unavailable: {e}\n".encode())
            return
        except (CacheError, ServiceError) as e:
            h._write(500, f"Internal Server Error: {e}\n".encode())
            return
        out = json_format.MessageToJson(resp).encode()
        code = resp.overall_code
        if code == rls_v3.RateLimitResponse.OK:
            status = 200
        elif code == rls_v3.RateLimitResponse.OVER_LIMIT:
            status = 429
        else:
            status = 500
        h._write(status, out, content_type="application/json")

    server.add_post("/json", handle)

    def _handle_release(h: _Handler) -> None:
        """POST /release — the concurrency Release surface: same
        RateLimitRequest JSON body as /json, but instead of admitting it
        DECREMENTS each matched concurrency descriptor's in-flight count
        (service.release). Answers {"released": n}."""
        try:
            length = int(h.headers.get("Content-Length", 0))
        except (TypeError, ValueError):
            h._write(400, b"Bad Request: invalid Content-Length\n")
            return
        body = h.rfile.read(length) if length > 0 else b""
        if not body:
            h._write(400, b"Bad Request: empty body\n")
            return
        try:
            req = json_format.Parse(body, rls_v3.RateLimitRequest())
        except json_format.ParseError as e:
            h._write(400, f"Bad Request: {e}\n".encode())
            return
        try:
            internal = proto_adapter.request_from_v3(req)
            released = service.release(internal)
        except (CacheError, ServiceError) as e:
            h._write(500, f"Internal Server Error: {e}\n".encode())
            return
        h._write(
            200,
            json.dumps({"released": released}).encode(),
            content_type="application/json",
        )

    def handle_release(h: _Handler) -> None:
        with tracing.start_http_server_span("/release", h.headers) as span:
            with tracing.activate(span):
                _handle_release(h)

    server.add_post("/release", handle_release)


def add_healthcheck(server: HttpServer, health: HealthChecker) -> None:
    def handle(h: _Handler) -> None:
        status, body = health.http_response()
        h._write(status, body.encode())

    server.add_get("/healthcheck", handle)


def new_debug_server(
    host: str,
    port: int,
    stats_store,
    enable_metrics: bool = True,
    profile_dir: str = "",
) -> HttpServer:
    """The debug-port suite (server_impl.go:217-250); /rlconfig is added by
    the runner via Server.add_debug_endpoint (runner.go:108-113).

    enable_metrics mounts GET /metrics — Prometheus text exposition
    rendered straight from the stats store (stats/prometheus.py), making
    the statsd -> prom-statsd-exporter hop optional. DEBUG_METRICS_ENABLED
    turns it off for deployments that must not expose a scrape surface.

    profile_dir (TPU_PROFILE_DIR): when set, GET /debug/profile?ms=N
    captures a jax.profiler device trace for N milliseconds into that
    directory — the on-demand view of what the dispatch owner loop keeps
    the device doing, with the program's own ratelimit.* host spans
    (tracing/host.py) on the same clock. Empty leaves the endpoint mounted
    but disabled."""
    server = HttpServer(host, port, "debug")

    def handle_stats(h: _Handler) -> None:
        h._write(
            200,
            json.dumps(stats_store.debug_snapshot(), indent=2).encode(),
            content_type="application/json",
        )

    def handle_metrics(h: _Handler) -> None:
        from ..stats import prometheus

        h._write(
            200,
            prometheus.render(stats_store).encode(),
            content_type=prometheus.CONTENT_TYPE,
        )

    def handle_pprof(h: _Handler) -> None:
        frames = sys._current_frames()
        out = []
        for thread in threading.enumerate():
            frame = frames.get(thread.ident)
            out.append(f"--- thread {thread.name} (id {thread.ident}) ---")
            if frame is not None:
                out.extend(line.rstrip() for line in traceback.format_stack(frame))
        h._write(200, ("\n".join(out) + "\n").encode())

    def handle_index(h: _Handler) -> None:
        lines = ["/debug endpoints:"] + [f"  {e}" for e in server.endpoints()]
        h._write(200, ("\n".join(lines) + "\n").encode())

    def handle_traces(h: _Handler) -> None:
        h._write(
            200,
            tracing.global_tracer().dump_json().encode(),
            content_type="application/json",
        )

    def handle_journeys(h: _Handler) -> None:
        """Tail-sampled flight recorder export (tracing/journeys.py):
        retained slow/shed/deadline/fault/over-limit journeys with
        per-stage ns timestamps, plus the per-thread recent rings.
        Renders offline via tools/journey_report.py."""
        from ..tracing import journeys

        recorder = journeys.global_recorder()
        if recorder is None:
            body = (
                '{"enabled": false, "retained": [], "recent": {}}\n'
            )
        else:
            body = recorder.dump_json()
        h._write(200, body.encode(), content_type="application/json")

    # one device profile at a time (same rationale as the CPU sampler)
    jax_profile_running = threading.Lock()

    def handle_jax_profile(h: _Handler) -> None:
        """GET /debug/profile?ms=N — capture a jax.profiler trace of the
        owner loop for N milliseconds into TPU_PROFILE_DIR (viewable in
        TensorBoard/Perfetto). Disabled (404) until the knob is set: the
        profiler costs real device throughput and writes to disk, so it
        must be an explicit operator opt-in."""
        if not profile_dir:
            h._write(
                404,
                b"device profiling disabled: set TPU_PROFILE_DIR\n",
            )
            return
        if not jax_profile_running.acquire(blocking=False):
            h._write(429, b"a device profile is already running; retry later\n")
            return
        try:
            query = urllib.parse.parse_qs(urllib.parse.urlparse(h.path).query)
            try:
                ms = min(float(query.get("ms", ["100"])[0]), 30_000.0)
            except ValueError as e:
                h._write(400, f"bad query parameter: {e}\n".encode())
                return
            import jax

            try:
                jax.profiler.start_trace(profile_dir)
                time.sleep(max(0.0, ms) / 1e3)
            finally:
                jax.profiler.stop_trace()
            h._write(
                200,
                json.dumps(
                    {"profile_dir": profile_dir, "ms": ms}
                ).encode(),
                content_type="application/json",
            )
        except Exception as e:  # noqa: BLE001 - profiling must not crash serving
            h._write(500, f"device profile failed: {e}\n".encode())
        finally:
            jax_profile_running.release()

    # One profile at a time (pprof semantics): N concurrent sampling loops
    # would each poll sys._current_frames() under the GIL, multiplying the
    # serve-path cost of a single profile by N.
    profile_running = threading.Lock()

    def handle_profile(h: _Handler) -> None:
        """On-demand CPU profile (the pprof /debug/pprof/profile analog,
        server_impl.go:219-224): a statistical sampler over ALL threads for
        ?seconds=N at ?hz=F, emitted in collapsed-stack ("folded") format —
        one `frame;frame;frame count` line per distinct stack, loadable by
        flamegraph.pl / speedscope / pprof's collapsed importer. A sampler
        (not cProfile) because the hot path runs on worker threads, which
        deterministic profilers can't attach to retroactively."""
        if not profile_running.acquire(blocking=False):
            h._write(429, b"a profile is already running; retry later\n")
            return
        try:
            _run_profile(h)
        finally:
            profile_running.release()

    def _run_profile(h: _Handler) -> None:
        query = urllib.parse.parse_qs(urllib.parse.urlparse(h.path).query)
        try:
            seconds = min(float(query.get("seconds", ["5"])[0]), 60.0)
            hz = min(float(query.get("hz", ["100"])[0]), 1000.0)
        except ValueError as e:
            h._write(400, f"bad query parameter: {e}\n".encode())
            return
        interval = 1.0 / max(hz, 1.0)
        me = threading.get_ident()
        counts: dict[tuple, int] = {}
        deadline = time.monotonic() + seconds
        while time.monotonic() < deadline:
            for tid, frame in sys._current_frames().items():
                if tid == me:
                    continue
                stack = []
                while frame is not None:
                    code = frame.f_code
                    stack.append(
                        f"{code.co_filename.rsplit('/', 1)[-1]}:"
                        f"{frame.f_lineno}:{code.co_name}"
                    )
                    frame = frame.f_back
                key = tuple(reversed(stack))
                counts[key] = counts.get(key, 0) + 1
            time.sleep(interval)
        body = "".join(
            ";".join(stack) + f" {n}\n"
            for stack, n in sorted(counts.items(), key=lambda kv: -kv[1])
        )
        h._write(200, body.encode())

    def handle_heap(h: _Handler) -> None:
        """Heap snapshot (the pprof /debug/pprof/heap analog) via
        tracemalloc. Arming is an explicit opt-in — ?start=1 begins tracing,
        a later plain GET returns the top allocation sites, ?stop=1 disarms.
        A bare GET never changes state (a metrics scraper or the endpoint
        index crawler hitting this URL must not leave allocation tracking —
        which costs real throughput — armed forever)."""
        import tracemalloc

        query = urllib.parse.parse_qs(urllib.parse.urlparse(h.path).query)
        if query.get("stop", ["0"])[0] in ("1", "true"):
            if tracemalloc.is_tracing():
                tracemalloc.stop()
            h._write(
                200,
                json.dumps({"status": "tracemalloc stopped"}).encode(),
                content_type="application/json",
            )
            return
        if query.get("start", ["0"])[0] in ("1", "true"):
            if not tracemalloc.is_tracing():
                tracemalloc.start(10)
            h._write(
                200,
                json.dumps(
                    {
                        "status": "tracemalloc armed; GET again for a "
                        "snapshot, ?stop=1 to disarm"
                    }
                ).encode(),
                content_type="application/json",
            )
            return
        if not tracemalloc.is_tracing():
            h._write(
                200,
                json.dumps(
                    {
                        "status": "tracemalloc not armed; GET ?start=1 to "
                        "begin tracing (read-only GETs never arm it)"
                    }
                ).encode(),
                content_type="application/json",
            )
            return
        try:
            top_n = min(int(query.get("top", ["50"])[0]), 500)
        except ValueError as e:
            h._write(400, f"bad query parameter: {e}\n".encode())
            return
        current, peak = tracemalloc.get_traced_memory()
        stats = tracemalloc.take_snapshot().statistics("lineno")[:top_n]
        h._write(
            200,
            json.dumps(
                {
                    "traced_current_bytes": current,
                    "traced_peak_bytes": peak,
                    "top": [
                        {
                            "file": s.traceback[0].filename,
                            "line": s.traceback[0].lineno,
                            "size_bytes": s.size,
                            "allocations": s.count,
                        }
                        for s in stats
                    ],
                },
                indent=2,
            ).encode(),
            content_type="application/json",
        )

    server.add_get("/stats", handle_stats)
    if enable_metrics:
        server.add_get("/metrics", handle_metrics)
    server.add_get("/debug/pprof/", handle_pprof)
    server.add_get("/debug/pprof/profile", handle_profile)
    server.add_get("/debug/pprof/heap", handle_heap)
    server.add_get("/debug/traces", handle_traces)
    server.add_get("/debug/journeys", handle_journeys)
    server.add_get("/debug/profile", handle_jax_profile)
    server.add_get("/", handle_index)
    return server


def add_chaos_admin(server: HttpServer, fault_injector, time_source) -> None:
    """Mount the chaos-campaign admin surface on a debug server:

        GET  /debug/faults   live rule set + per-rule hit/fire state
                             (FaultInjector.describe())
        POST /debug/faults   replace the rule set at runtime — body is a
                             FAULT_INJECT spec string, or JSON
                             {"spec": str, "seed": int?}; a junk spec
                             answers 400 and changes nothing (the same
                             fail-loud contract as boot parsing)
        GET  /debug/clock    the process clock: unix_now + current skew
        POST /debug/clock    step/drift the process clock — JSON
                             {"offset_s": float?, "drift_ppm": float?};
                             {} resets the skew

    This is what replaces boot-time-only FAULT_INJECT for chaos
    campaigns: the nemesis flips faults and skews clocks on a LIVE
    process (runner.py and cmd/sidecar_cmd.py both mount it; the sidecar
    wire protocol exposes the same verbs as OP_FAULTS_SET/OP_CLOCK_SET)."""
    from ..testing.faults import parse_fault_spec

    def _read_body(h: _Handler) -> bytes:
        length = int(h.headers.get("Content-Length", "0") or "0")
        return h.rfile.read(length) if length > 0 else b""

    def _json(h: _Handler, status: int, doc) -> None:
        h._write(
            status,
            json.dumps(doc, indent=2).encode(),
            content_type="application/json",
        )

    def handle_faults_get(h: _Handler) -> None:
        _json(h, 200, fault_injector.describe())

    def handle_faults_post(h: _Handler) -> None:
        raw = _read_body(h).decode("utf-8", "replace").strip()
        spec, seed = raw, None
        if raw.startswith("{"):
            try:
                doc = json.loads(raw)
                spec = str(doc.get("spec", ""))
                seed = doc.get("seed")
            except (ValueError, AttributeError) as e:
                _json(h, 400, {"error": f"bad JSON body: {e}"})
                return
        try:
            rules = parse_fault_spec(spec)
            fault_injector.configure(
                rules, seed=None if seed is None else int(seed)
            )
        except ValueError as e:
            _json(h, 400, {"error": str(e)})
            return
        _json(h, 200, fault_injector.describe())

    def handle_clock_get(h: _Handler) -> None:
        skew = getattr(time_source, "skew", None)
        _json(
            h,
            200,
            {
                "unix_now": time_source.unix_now(),
                "skewable": skew is not None,
                "skew": skew() if skew is not None else None,
            },
        )

    def handle_clock_post(h: _Handler) -> None:
        set_skew = getattr(time_source, "set_skew", None)
        if set_skew is None:
            _json(h, 400, {"error": "process time source is not skewable"})
            return
        raw = _read_body(h).decode("utf-8", "replace").strip() or "{}"
        try:
            doc = json.loads(raw)
            offset_s = float(doc.get("offset_s", 0.0))
            drift_ppm = float(doc.get("drift_ppm", 0.0))
        except (ValueError, TypeError, AttributeError) as e:
            _json(h, 400, {"error": f"bad clock body: {e}"})
            return
        set_skew(offset_s=offset_s, drift_ppm=drift_ppm)
        handle_clock_get(h)

    server.add_get("/debug/faults", handle_faults_get)
    server.add_post("/debug/faults", handle_faults_post)
    server.add_get("/debug/clock", handle_clock_get)
    server.add_post("/debug/clock", handle_clock_post)
