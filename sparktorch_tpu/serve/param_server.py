"""HBM-resident parameter server for asynchronous (hogwild) training.

Reference: ``sparktorch/server.py`` — a Flask app in a forked process
on the driver holding the canonical model in shared CPU memory
(``share_memory()``, server.py:83), with routes ``GET /`` (liveness,
:89-91), ``GET /parameters`` (full dill state_dict, :93-100),
``POST /update`` (install grads, ``optimizer.step()`` under an RWLock
that both read & write paths take as *write*, :125-147), and
``POST /losses`` (windowed-average early stop, :102-123). It tolerates
up to 10 update errors before raising (:139-142).

TPU-native redesign:

- Canonical params live as **device arrays in HBM** behind a
  :class:`VersionedSlot` — reads are lock-free immutable snapshots,
  so pulls never contend with applies (the reference serializes them,
  SURVEY §5 "both take the write lock").
- Applies run on a **single writer thread** draining a FIFO queue
  through one jitted ``optax`` update — the principled version of
  hogwild's "just step whenever grads arrive", keeping the optimizer
  math on-device and race-free by construction.
- Pulls are **version-tagged**: a client that already holds version N
  gets "nothing newer" instead of a full redundant weight transfer —
  eliminating the reference's 2×model-size-per-iteration HTTP
  pathology (``hogwild.py:103,130``; SURVEY §3.2).
- Transport is split from state: in-process calls for workers in the
  same runtime, and a stdlib-HTTP wire (:class:`ParamServerHttp`)
  with the reference's four routes for remote workers (no Flask in
  this image; the wire format is dill like the reference's).
"""

from __future__ import annotations

import json
import queue
import socket as _socket
import threading
import time
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer
from typing import Any, Optional, Tuple

import dill
import jax
import numpy as np

from sparktorch_tpu.ft import chaos as _chaos
from sparktorch_tpu.net import wire as binwire
from sparktorch_tpu.obs import goodput as _goodput
from sparktorch_tpu.obs import (
    PROMETHEUS_CONTENT_TYPE,
    Telemetry,
    render_prometheus,
    wall_ts,
)
from sparktorch_tpu.obs import rpctrace as _rpctrace
from sparktorch_tpu.utils.early_stopper import EarlyStopping
from sparktorch_tpu.utils.locks import VersionedSlot
from sparktorch_tpu.utils.serde import ModelSpec, deserialize_model

MAX_TOLERATED_ERRORS = 10  # server.py:139-142 parity


class ParameterServer:
    """Driver-hosted canonical-parameter holder + async applier."""

    def __init__(
        self,
        torch_obj,
        window_len: int = 3,
        early_stop_patience: int = -1,
        acquire_lock: bool = True,
        device: Optional[jax.Device] = None,
        seed: int = 0,
        telemetry: Optional[Telemetry] = None,
    ):
        # The server deserializes its own model copy, like
        # server.py:44-51 — but params go straight to device HBM.
        self.spec: ModelSpec = deserialize_model(torch_obj)
        # Server-scoped bus (not the process global): each server's
        # counters are its own, so a test or driver hosting several
        # servers never cross-talks. The HTTP wire serves this very
        # instance from /metrics.
        self.telemetry = telemetry or Telemetry(run_id="param_server")
        self.device = device or jax.devices()[0]
        self.acquire_lock = acquire_lock  # parity knob; applies are
        # always serialized by the single writer thread.

        self._tx = self.spec.make_optimizer()
        rng = jax.random.key(seed)
        variables = dict(self.spec.init_params(rng))
        params = variables.pop("params", variables)
        params = jax.device_put(params, self.device)
        self._model_state = jax.device_put(variables, self.device)
        self._opt_state = jax.device_put(self._tx.init(params), self.device)
        self.slot = VersionedSlot(params)

        # One compiled apply for the life of the server. Grads arrive
        # in whatever dtype the wire used (bf16 from HttpTransport's
        # compressed pushes); cast up to the param dtype before the
        # optimizer update so moments stay full precision.
        def _apply(params, opt_state, grads):
            grads = jax.tree.map(
                lambda g, p: g.astype(p.dtype), grads, params
            )
            updates, new_opt = self._tx.update(grads, opt_state, params)
            import optax

            return optax.apply_updates(params, updates), new_opt

        self._apply_fn = jax.jit(_apply)

        # Windowed early stop (server.py:102-123 parity).
        self.window_len = max(1, window_len)
        self._losses: list = []
        self._stopper = (
            EarlyStopping(patience=early_stop_patience)
            if early_stop_patience and early_stop_patience > 0
            else None
        )
        self._stop_flag = False
        self._loss_lock = threading.Lock()

        self._queue: "queue.Queue" = queue.Queue()
        self._errors = 0
        self._failed: Optional[BaseException] = None
        self._applied = 0
        self._running = True
        self._writer = threading.Thread(target=self._apply_loop, daemon=True)
        self._writer.start()

    # ------------------------------------------------------------------
    # State access
    # ------------------------------------------------------------------

    def get_parameters(self, have_version: int = -1) -> Optional[Tuple[int, Any]]:
        """Immutable snapshot pull; None if the client is up to date.

        Parity: ``GET /parameters`` (server.py:93-100), minus the
        redundant-transfer pathology.
        """
        snap = self.slot.read_if_newer(have_version)
        self.telemetry.counter("param_server.pulls")
        if snap is not None:
            self.telemetry.counter("param_server.pull_fresh")
        return snap

    def model_state(self):
        return self._model_state

    @property
    def applied_updates(self) -> int:
        return self._applied

    # ------------------------------------------------------------------
    # Gradient path
    # ------------------------------------------------------------------

    def push_gradients(self, grads, wait: bool = True,
                       timeout: float = 60.0, trace_ctx=None) -> None:
        """Enqueue a gradient pytree for the writer thread.

        Parity: ``POST /update`` (server.py:125-147) — the reference
        applies ``optimizer.step()`` synchronously inside the request,
        so a worker's next pull always reflects its own push. With
        ``wait=True`` (default) the same guarantee holds here: the
        call returns once THIS gradient is applied. Applies remain
        FIFO-serialized by the single writer thread; workers never
        barrier against each other (hogwild semantics preserved).
        ``wait=False`` gives fully fire-and-forget pushes.

        ``trace_ctx`` (a sampled span context from the wire) rides the
        queue item so the writer thread can attribute THIS request's
        queue-wait and apply as child spans — the split that tells a
        slow push apart from a backed-up writer.
        """
        if self._failed is not None:
            raise RuntimeError("parameter server failed") from self._failed
        done = threading.Event() if wait else None
        self._queue.put((grads, done, trace_ctx,
                         wall_ts(), time.perf_counter()))  # lint-obs: ok (request-latency histogram clock pair, not a ledger region)
        self.telemetry.counter("param_server.pushes")
        self.telemetry.gauge("param_server.queue_depth", self._queue.qsize())
        if done is not None and not done.wait(timeout):
            raise TimeoutError("parameter server apply timed out")

    def _apply_loop(self):
        tracer = _rpctrace.tracer_for(self.telemetry)
        while self._running:
            try:
                grads, done, tctx, enq_ts, enq_t0 = self._queue.get(
                    timeout=0.1)
            except queue.Empty:
                continue
            try:
                t0 = time.perf_counter()  # lint-obs: ok (request-latency histogram clock pair, not a ledger region)
                # Queue-wait attribution: enqueue happened on a handler
                # thread, the pop here — the after-the-fact record is
                # the only honest way to span it.
                tracer.record("queue_wait", tctx, enq_ts, t0 - enq_t0,
                              kind="server")
                # A serving rank's productive seconds are its applies:
                # the same writer stamp the rpc trace spans, attributed
                # into the ambient goodput ledger's compute bucket
                # (no-op when no ledger is installed on this rank).
                with tracer.child_span("apply", tctx, kind="server"), \
                        _goodput.span("compute", {"site": "ps_apply"}):
                    version, params = self.slot.read()
                    grads = jax.device_put(grads, self.device)
                    new_params, new_opt = self._apply_fn(
                        params, self._opt_state, grads
                    )
                    self._opt_state = new_opt
                    self.slot.swap(new_params)
                self._applied += 1
                self.telemetry.counter("param_server.applies")
                self.telemetry.observe("param_server.apply_s",
                                       time.perf_counter() - t0)  # lint-obs: ok (request-latency histogram clock pair, not a ledger region)
                self.telemetry.gauge("param_server.version", version + 1)
            except Exception as e:  # tolerate a bounded error count
                self._errors += 1
                self.telemetry.counter("param_server.apply_errors")
                if self._errors > MAX_TOLERATED_ERRORS:
                    self._failed = e
                    self._running = False
            finally:
                if done is not None:
                    done.set()
                self._queue.task_done()

    def drain(self, timeout: float = 30.0) -> None:
        """Block until all queued gradients are fully applied (not just
        popped — ``unfinished_tasks`` covers the in-flight apply)."""
        import time

        deadline = time.monotonic() + timeout
        while self._queue.unfinished_tasks and time.monotonic() < deadline:
            time.sleep(0.005)

    # ------------------------------------------------------------------
    # Early stopping
    # ------------------------------------------------------------------

    def post_loss(self, loss: float) -> bool:
        """Windowed-average early-stop vote. Returns True => stop.

        Parity: ``POST /losses`` (server.py:102-123): collect one loss
        per worker, average a full window, feed the patience tracker.
        """
        self.telemetry.counter("param_server.losses_posted")
        with self._loss_lock:
            if self._stop_flag:
                return True
            if self._stopper is None:
                return False
            self._losses.append(float(loss))
            if len(self._losses) >= self.window_len:
                avg = float(np.mean(self._losses))
                self._losses.clear()
                if self._stopper.step(avg):
                    self._stop_flag = True
        return self._stop_flag

    @property
    def should_stop(self) -> bool:
        return self._stop_flag

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------

    def stop(self):
        self._running = False
        if self._writer.is_alive():
            self._writer.join(timeout=5.0)

    def final_state(self):
        """(params, model_state) after draining pending applies —
        what ``hogwild.train`` pulls at the end (hogwild.py:179-182)."""
        self.drain()
        _, params = self.slot.read()
        return params, self._model_state


# ---------------------------------------------------------------------------
# HTTP wire (stdlib; the reference used Flask — server.py:79-149)
# ---------------------------------------------------------------------------


def _to_host(tree):
    return jax.tree.map(lambda a: np.asarray(a), tree)


class _KeepAliveHTTPServer(ThreadingHTTPServer):
    """ThreadingHTTPServer that can actually STOP: with HTTP/1.1
    keep-alive, handler threads park in a blocking read on live client
    sockets, and ``shutdown()`` only stops the accept loop — the old
    connections (and their threads) would survive a ``stop()`` and
    keep serving a supposedly-dead server, which masks restarts (a
    client's "reconnect after server restart" would silently talk to
    the zombie). Track live request sockets and shut them down on
    stop — the same live-fd handling the native gang coordinator does
    in ``gang_server_stop``."""

    daemon_threads = True

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._live_requests: set = set()
        self._live_lock = threading.Lock()

    def process_request(self, request, client_address):
        with self._live_lock:
            self._live_requests.add(request)
        super().process_request(request, client_address)

    def shutdown_request(self, request):
        with self._live_lock:
            self._live_requests.discard(request)
        super().shutdown_request(request)

    def close_all_connections(self):
        with self._live_lock:
            live = list(self._live_requests)
        for sock in live:
            try:
                sock.shutdown(_socket.SHUT_RDWR)
            except OSError:
                pass  # already closing


class ParamServerHttp:
    """Expose a :class:`ParameterServer` over HTTP/1.1.

    Routes mirror the reference wire (hogwild.py:31-62):
    ``GET /`` liveness, ``GET /parameters`` (dill, honors the
    ``X-Have-Version`` header with 204 when not newer),
    ``POST /update`` (dill grads), ``POST /losses`` (dill float ->
    dill {'stop': bool}).

    Binary-wire routes (:mod:`sparktorch_tpu.net.wire`) render from
    the SAME version-keyed snapshot as the dill ones, so a mixed gang
    (dill workers next to binary workers) trains against one coherent
    server: ``GET /parameters.bin`` (framed tensors, ``X-Have-Version``
    honored with a real 304), ``POST /update.bin`` (framed gradient
    tree, quantized tensors dequantized at decode), and
    ``POST /losses.json`` (JSON early-stop vote). The server speaks
    HTTP/1.1 so binary clients keep one connection alive for the whole
    run. Every wire route feeds ``wire_bytes_total{route,dir}`` and a
    per-route latency histogram into the telemetry bus.

    Observability routes beyond the reference: ``GET /metrics`` serves
    the server's telemetry as Prometheus exposition text (scrapeable),
    and ``GET /telemetry`` the same snapshot as JSON — both rendered
    from ONE ``Telemetry.snapshot()``, so a scrape can never disagree
    with the JSONL dump of the same server.

    Fleet mode (:mod:`sparktorch_tpu.serve.fleet`): when the backing
    server exposes ``render_delta`` (a :class:`ParamShardServer`),
    ``GET /delta.bin`` serves per-tensor delta frames — only the
    leaves whose version advanced past the client's
    ``X-Have-Version``, optionally int8-quantized with server-side
    error feedback (``X-Pull-Quant: int8``). Every delta reply (304
    included) carries ``X-Slot-Epoch`` (the slot's boot nonce — a
    restarted/rebuilt server is detected by epoch change, never by
    version arithmetic) and, when ``ring_version_fn`` is given,
    ``X-Ring-Version`` so clients learn about shard add/drain without
    polling. ``shard`` labels every wire metric series with the shard
    id, and ``extra_json_routes`` mounts small JSON control routes
    (the fleet's ``/fleet.json`` topology document).
    """

    def __init__(self, server: ParameterServer, host: str = "127.0.0.1",
                 port: int = 3000, shard: Optional[str] = None,
                 extra_json_routes=None, ring_version_fn=None):
        self.server = server
        self.host = host
        self.port = port
        self.shard = str(shard) if shard is not None else None
        self.extra_json_routes = dict(extra_json_routes or {})
        self.ring_version_fn = ring_version_fn
        self._httpd: Optional[ThreadingHTTPServer] = None
        self._thread: Optional[threading.Thread] = None

    def start(self):
        ps = self.server
        # Version-keyed cache of the host snapshot and its rendered
        # wire bodies: materializing device params costs a full host
        # download — pay it once per VERSION, not once per worker
        # pull; each wire
        # format (dill / binary frame) then renders lazily from the
        # one host tree, so a mixed gang shares a single download.
        # The slot's version tag makes staleness detection free.
        wire_cache: dict = {"version": None, "host": None,
                            "dill": None, "bin": None}
        wire_lock = threading.Lock()
        # Run-ID correlation: frames this server sends carry the
        # 16-bit tag of its bus run_id; a push tagged with a DIFFERENT
        # nonzero tag is a worker from another run (recycled port,
        # stale supervisor) — counted + flagged, but still applied
        # (the tag is a join key for the collector, not an ACL).
        from sparktorch_tpu.obs.collector import run_tag as _run_tag

        server_tag = _run_tag(ps.telemetry.run_id)
        # Request tracing: sampled span contexts arrive as the binary
        # frame's trace extension or the X-Trace-Context header; every
        # handler contributes a SERVE child span (+ decode/render/
        # queue_wait/apply below it) on the server's own bus — the
        # collector stitches them back under the worker's root by
        # trace_id.
        tracer = _rpctrace.tracer_for(ps.telemetry)

        def _cached_body(fmt: str):
            """(version, body) from ONE slot read — the handler's
            freshness decision and the served bytes share a source of
            truth. Materialization and rendering happen UNDER the
            lock: when a new version lands and every worker pulls at
            once, late arrivals block briefly and reuse the one body
            instead of each paying the multi-second host download (and
            a slow dump can never overwrite a newer cached entry)."""
            with wire_lock:
                version, params = ps.slot.read()
                if wire_cache["version"] != version:
                    wire_cache.update(version=version,
                                      host=_to_host(params),
                                      dill=None, bin=None)
                if wire_cache[fmt] is None:
                    if fmt == "dill":
                        wire_cache["dill"] = dill.dumps(
                            (version, wire_cache["host"])
                        )
                    else:
                        wire_cache["bin"] = binwire.frame_bytes(
                            binwire.encode(wire_cache["host"],
                                           version=version,
                                           run_tag=server_tag)
                        )
                return version, wire_cache[fmt]

        psh = self
        shard_label = self.shard
        extra_json = self.extra_json_routes
        ring_version_fn = self.ring_version_fn

        def _record_wire(route: str, direction: str, nbytes: int,
                         seconds: float) -> None:
            """Per-route byte/latency accounting on the bus: the
            `/metrics` series the ISSUE names (wire_bytes_total plus a
            push/pull latency histogram per route). Fleet shards add
            a ``shard`` label so the per-shard series never alias."""
            labels = {"route": route, "dir": direction}
            hist_labels = {"route": route}
            if shard_label is not None:
                labels["shard"] = shard_label
                hist_labels["shard"] = shard_label
            ps.telemetry.counter("param_server.wire_bytes_total", nbytes,
                                 labels=labels)
            ps.telemetry.observe("param_server.wire_latency_s", seconds,
                                 labels=hist_labels)

        def _fire_shard_chaos(handler, route: str) -> bool:
            """The fleet's seeded shard-kill site: a chaos config can
            take THIS shard's HTTP frontend down at its Nth request.
            Returns True when the request must be aborted (connection
            dropped, no reply — exactly what a dying shard looks
            like from the client side)."""
            if shard_label is None:
                return False
            act = _chaos.fire("fleet.shard", shard=shard_label, route=route)
            if act and act.get("delay"):
                # Straggler-shard fault: the reply is correct, just
                # late. Slept BEFORE the route's serve span starts, so
                # a traced request sees it as the shard HOP's self
                # time (client-side `shard_pull` span) — network-shaped
                # latency lands on the hop, server work on `serve`, and
                # the critical path names this shard either way.
                time.sleep(float(act["delay"]))
            if act and act.get("die"):
                # stop() from a separate thread: it joins handler
                # machinery this very thread is part of.
                threading.Thread(target=psh.stop, daemon=True).start()
                handler.close_connection = True
                return True
            return False

        class Handler(BaseHTTPRequestHandler):
            # Keep-alive: binary transports hold ONE connection for a
            # whole training run instead of a TCP setup per call.
            protocol_version = "HTTP/1.1"

            def log_message(self, *a):  # quiet, like werkzeug->ERROR
                pass  # (server.py:28-30 parity)

            def _send(self, code: int, body: bytes = b"",
                      content_type: Optional[str] = None,
                      extra_headers=None):
                self.send_response(code)
                if content_type:
                    self.send_header("Content-Type", content_type)
                for k, v in (extra_headers or {}).items():
                    self.send_header(k, str(v))
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                if body:
                    self.wfile.write(body)

            def _trace_ctx(self, raw: Optional[bytes] = None):
                """The request's span context: the binary frame's
                trace extension when a body is given (the push path —
                the frame is authoritative), else the HTTP header.
                None (untraced) on anything absent or malformed — a
                garbled context must never fail a request."""
                if raw:
                    try:
                        ctx = binwire.frame_trace(raw)
                    except binwire.WireError:
                        ctx = None
                    if ctx is not None:
                        return ctx
                return _rpctrace.SpanContext.from_header(
                    self.headers.get(_rpctrace.TRACE_HEADER))

            def _serve_span(self, route: str, ctx):
                ann = {"route": route}
                if shard_label is not None:
                    ann["shard"] = shard_label
                return tracer.child_span("serve", ctx, kind="server",
                                         **ann)

            def _delta_headers(self) -> dict:
                """Resync metadata on EVERY delta reply (304 too): the
                slot epoch catches rebuilt server state, the ring
                version catches shard add/drain."""
                out = {}
                epoch = getattr(ps.slot, "epoch", None)
                if epoch is not None:
                    out["X-Slot-Epoch"] = str(int(epoch))
                if ring_version_fn is not None:
                    out["X-Ring-Version"] = str(int(ring_version_fn()))
                return out

            def do_GET(self):
                route = self.path.split("?", 1)[0]
                if _fire_shard_chaos(self, route):
                    return
                ps.telemetry.counter("param_server.http_requests",
                                     labels={"route": route})
                if route == "/delta.bin" \
                        and hasattr(ps, "render_delta"):
                    with self._serve_span(route, self._trace_ctx()) as ssp:
                        t0 = time.perf_counter()  # lint-obs: ok (request-latency histogram clock pair, not a ledger region)
                        have = int(self.headers.get("X-Have-Version",
                                                    "-1"))
                        quant = self.headers.get("X-Pull-Quant") or None
                        try:
                            with tracer.child_span("render", ssp.ctx,
                                                   kind="server"):
                                _version, body = ps.render_delta(
                                    have, quant=quant,
                                    run_tag=server_tag
                                )
                        except ValueError:
                            self._send(400)
                            return
                        hdrs = self._delta_headers()
                        if body is None:
                            self._send(304, extra_headers=hdrs)
                            _record_wire(route, "tx", 0,
                                         time.perf_counter() - t0)  # lint-obs: ok (request-latency histogram clock pair, not a ledger region)
                            return
                        act = _chaos.fire("param_server.pull",
                                          route=route)
                        if act and act.get("truncate"):
                            body = body[: max(1, len(body) // 2)]
                        self._send(200, body,
                                   content_type=binwire.CONTENT_TYPE,
                                   extra_headers=hdrs)
                        _record_wire(route, "tx", len(body),
                                     time.perf_counter() - t0)  # lint-obs: ok (request-latency histogram clock pair, not a ledger region)
                    return
                if route in extra_json:
                    try:
                        doc = extra_json[route]()
                    except Exception:
                        self._send(500)
                        return
                    self._send(200, json.dumps(doc).encode(),
                               content_type="application/json")
                    return
                if route == "/":
                    self._send(200, b"sparktorch-tpu parameter server")
                elif route in ("/parameters", "/parameters.bin"):
                    with self._serve_span(route, self._trace_ctx()) as ssp:
                        t0 = time.perf_counter()  # lint-obs: ok (request-latency histogram clock pair, not a ledger region)
                        have = int(self.headers.get("X-Have-Version",
                                                    "-1"))
                        binary = route.endswith(".bin")
                        with tracer.child_span("render", ssp.ctx,
                                               kind="server"):
                            version, body = _cached_body(
                                "bin" if binary else "dill")
                        if version <= have:
                            # 304 on the binary wire (true HTTP
                            # semantics); the dill route keeps its
                            # original 204 so old clients stay
                            # byte-compatible.
                            self._send(304 if binary else 204)
                            _record_wire(route, "tx", 0,
                                         time.perf_counter() - t0)  # lint-obs: ok (request-latency histogram clock pair, not a ledger region)
                        else:
                            act = _chaos.fire("param_server.pull",
                                              route=route)
                            if act and act.get("truncate"):
                                # Injected torn response: the declared
                                # length is honest for the bytes sent,
                                # so the CLIENT'S frame check (WireError
                                # on a short payload) is what must
                                # catch it.
                                body = body[: max(1, len(body) // 2)]
                            self._send(200, body,
                                       content_type=binwire.CONTENT_TYPE
                                       if binary else None)
                            _record_wire(route, "tx", len(body),
                                         time.perf_counter() - t0)  # lint-obs: ok (request-latency histogram clock pair, not a ledger region)
                elif route == "/metrics":
                    text = render_prometheus(ps.telemetry.snapshot())
                    self._send(200, text.encode(),
                               content_type=PROMETHEUS_CONTENT_TYPE)
                elif route == "/telemetry":
                    self._send(200,
                               json.dumps(ps.telemetry.snapshot()).encode(),
                               content_type="application/json")
                else:
                    self._send(404)

            def do_POST(self):
                # Label with the query-stripped route (like do_GET):
                # raw paths would split one route across series and
                # let a client grow label cardinality without bound.
                route = self.path.split("?", 1)[0]
                if _fire_shard_chaos(self, route):
                    return
                ps.telemetry.counter("param_server.http_requests",
                                     labels={"route": route})
                length = int(self.headers.get("Content-Length", "0"))
                raw = self.rfile.read(length)
                if route == "/update":
                    with self._serve_span(route, self._trace_ctx()) as ssp:
                        t0 = time.perf_counter()  # lint-obs: ok (request-latency histogram clock pair, not a ledger region)
                        try:
                            # Chaos 500s fire here — inside the try, so
                            # the forced error takes the same path a
                            # real apply failure would (a 500, nothing
                            # else).
                            _chaos.fire("param_server.update",
                                        route=route)
                            with tracer.child_span("decode", ssp.ctx,
                                                   kind="server"):
                                grads = dill.loads(raw)
                            ps.push_gradients(grads, trace_ctx=ssp.ctx)
                            self._send(200, b"OK")
                            _record_wire(route, "rx", len(raw),
                                         time.perf_counter() - t0)  # lint-obs: ok (request-latency histogram clock pair, not a ledger region)
                        except Exception:
                            ssp.annotate(http_status=500)
                            self._send(500)
                elif route == "/update.bin":
                    with self._serve_span(route,
                                          self._trace_ctx(raw)) as ssp:
                        t0 = time.perf_counter()  # lint-obs: ok (request-latency histogram clock pair, not a ledger region)
                        try:
                            with tracer.child_span("decode", ssp.ctx,
                                                   kind="server"):
                                _version, grads = binwire.decode(raw)
                            frame_tag = binwire.frame_run_tag(raw)
                        except binwire.WireError:
                            # A malformed frame is the CLIENT's bug (or
                            # a truncated send): 400, and never counted
                            # against the server's tolerated apply
                            # errors.
                            ssp.annotate(http_status=400)
                            self._send(400)
                            return
                        if frame_tag and server_tag \
                                and frame_tag != server_tag:
                            ps.telemetry.counter(
                                "param_server.run_tag_mismatches_total"
                            )
                        try:
                            _chaos.fire("param_server.update",
                                        route=route)
                            ps.push_gradients(grads, trace_ctx=ssp.ctx)
                            self._send(200, b"OK")
                            _record_wire(route, "rx", len(raw),
                                         time.perf_counter() - t0)  # lint-obs: ok (request-latency histogram clock pair, not a ledger region)
                        except Exception:
                            ssp.annotate(http_status=500)
                            self._send(500)
                elif route == "/losses":
                    stop = ps.post_loss(dill.loads(raw))
                    self._send(200, dill.dumps({"stop": bool(stop)}))
                elif route == "/losses.json":
                    try:
                        loss = float(json.loads(raw)["loss"])
                    except (ValueError, KeyError, TypeError):
                        self._send(400)
                        return
                    stop = ps.post_loss(loss)
                    self._send(200,
                               json.dumps({"stop": bool(stop)}).encode(),
                               content_type="application/json")
                else:
                    self._send(404)

        self._httpd = _KeepAliveHTTPServer((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]  # resolve port 0
        self._thread = threading.Thread(target=self._httpd.serve_forever, daemon=True)
        self._thread.start()
        return self

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def stop(self):
        if self._httpd is not None:
            self._httpd.shutdown()
            # Drop live keep-alive connections too: a stopped server
            # must go DARK (clients redial a restarted one), not keep
            # answering through parked handler threads.
            self._httpd.close_all_connections()
            self._httpd.server_close()
            self._httpd = None
