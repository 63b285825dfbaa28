"""Python API over the native gang coordinator (native/gang.cpp).

Gang scheduling + rendezvous + failure detection for multi-host
bring-up — the native replacement for the reference's Spark JVM
barrier stage (``distributed.py:39-43``) and gloo TCP rendezvous on a
hardcoded port (``distributed.py:101-105``). The typical flow:

    # driver / host 0
    coord = GangCoordinator(world_size=4)
    # every host (including 0)
    worker = GangWorker(coord_host, coord.port, rank, my_addr)
    worker.barrier(0)                 # gang entry
    peers = worker.world()            # rank-ordered addresses
    jax.distributed.initialize(coordinator_address=peers[0], ...)

Heartbeats run on a daemon thread; a dead host flips every barrier
into a GangFailure, so surviving hosts fail fast instead of hanging
in an XLA collective.
"""

from __future__ import annotations

import ctypes
import os
import threading
import time
from typing import List, Optional

from sparktorch_tpu.native.build import load_library
from sparktorch_tpu.obs.heartbeat import HEARTBEAT_DIR_ENV, HeartbeatEmitter


class GangFailure(RuntimeError):
    pass


class GangMetricsExporter:
    """Tiny HTTP surface beside the gang coordinator (ROADMAP:
    "multi-host sync training has no HTTP surface yet").

    The param server already scrapes; this gives the SYNC/gang path
    its twin: ``GET /metrics`` serves the attached telemetry snapshot
    as Prometheus text with the heartbeat table folded in as per-rank
    gauges (liveness, step, last-seen age, step skew — derived at
    scrape time from the shared heartbeat directory, so a dead rank
    shows up as a growing age even though it stopped publishing), plus
    coordinator state (registered/failed/dead_rank) when a
    :class:`GangCoordinator` is attached. ``GET /telemetry`` is the
    same merged view as JSON; ``GET /heartbeats`` just the per-rank
    table. Runs on a daemon thread like :class:`ParamServerHttp`; all
    three pieces (telemetry, heartbeat dir, coordinator) are optional,
    so the exporter serves whatever the deployment actually has.
    """

    def __init__(self, heartbeat_dir: Optional[str] = None,
                 coordinator: Optional["GangCoordinator"] = None,
                 telemetry=None, host: str = "127.0.0.1", port: int = 0,
                 ctl=None):
        self.heartbeat_dir = heartbeat_dir or os.environ.get(HEARTBEAT_DIR_ENV)
        self.coordinator = coordinator
        self.telemetry = telemetry
        self.host = host
        self.port = port
        # Control surface (``POST /ctl``): a :class:`sparktorch_tpu.
        # ctl.CtlRegistry` (duck-typed — anything with ``check_token``
        # and ``handle``) lets an elastic controller manage this
        # process (kill/drain/resize verbs) over HTTP when it holds no
        # local handle on it. None = the route answers 404 (the
        # original read-only exporter).
        self.ctl = ctl
        self._httpd = None
        self._thread: Optional[threading.Thread] = None

    def _merged_snapshot(self) -> dict:
        from sparktorch_tpu.obs import Telemetry, gang_report

        tele = self.telemetry
        snap = (tele.snapshot() if tele is not None
                else Telemetry(run_id="gang_exporter").snapshot())
        gauges = snap.setdefault("gauges", {})
        if self.heartbeat_dir:
            report = gang_report(self.heartbeat_dir)
            snap["gang_report"] = report
            for rank, rec in report.get("ranks", {}).items():
                gauges[f"gang.hb_alive{{rank={rank}}}"] = (
                    1.0 if rec["alive"] else 0.0
                )
                gauges[f"gang.hb_last_seen_age_s{{rank={rank}}}"] = (
                    rec["last_seen_age_s"]
                )
                if rec.get("step") is not None:
                    gauges[f"gang.hb_step{{rank={rank}}}"] = float(rec["step"])
            if "step_skew" in report:
                gauges["gang.hb_step_skew"] = float(report["step_skew"])
            gauges["gang.hb_ranks"] = float(report.get("n_ranks", 0))
        coord = self.coordinator
        if coord is not None:
            gauges["gang.coordinator_registered"] = float(coord.registered)
            gauges["gang.coordinator_failed"] = 1.0 if coord.failed else 0.0
            gauges["gang.coordinator_dead_rank"] = float(coord.dead_rank)
            gauges["gang.coordinator_world_size"] = float(coord.world_size)
            gauges["gang.coordinator_generation"] = float(coord.generation)
            if getattr(coord, "run_id", None):
                # The gang run_id rides the scrape like a build_info
                # string, so a collector can correlate this exporter
                # with the rank streams without parsing REG lines.
                snap.setdefault("info", {})["gang.run_id"] = coord.run_id
        return snap

    def start(self) -> "GangMetricsExporter":
        import json as _json
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        from sparktorch_tpu.obs import (
            PROMETHEUS_CONTENT_TYPE,
            render_prometheus,
        )

        exporter = self

        class Handler(BaseHTTPRequestHandler):
            def log_message(self, *a):
                pass

            def _send(self, code: int, body: bytes = b"",
                      content_type: Optional[str] = None):
                self.send_response(code)
                if content_type:
                    self.send_header("Content-Type", content_type)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                if body:
                    self.wfile.write(body)

            def do_GET(self):
                route = self.path.split("?", 1)[0]
                if route == "/":
                    self._send(200, b"sparktorch-tpu gang exporter")
                elif route == "/metrics":
                    snap = exporter._merged_snapshot()
                    snap.pop("gang_report", None)  # gauges carry it
                    self._send(200, render_prometheus(snap).encode(),
                               content_type=PROMETHEUS_CONTENT_TYPE)
                elif route == "/telemetry":
                    self._send(200,
                               _json.dumps(
                                   exporter._merged_snapshot()).encode(),
                               content_type="application/json")
                elif route == "/heartbeats":
                    from sparktorch_tpu.obs import gang_report

                    report = (gang_report(exporter.heartbeat_dir)
                              if exporter.heartbeat_dir else {"n_ranks": 0,
                                                              "ranks": {},
                                                              "alive": []})
                    self._send(200, _json.dumps(report).encode(),
                               content_type="application/json")
                else:
                    self._send(404)

            def do_POST(self):
                route = self.path.split("?", 1)[0]
                if route != "/ctl" or exporter.ctl is None:
                    self._send(404)
                    return
                try:
                    length = int(self.headers.get("Content-Length", 0))
                    body = _json.loads(self.rfile.read(length) or b"{}")
                    if not isinstance(body, dict):
                        raise ValueError("ctl body must be an object")
                except (ValueError, TypeError) as e:
                    self._send(400, str(e).encode())
                    return
                if not exporter.ctl.check_token(
                        self.headers.get("X-Ctl-Token")):
                    self._send(403, b"bad ctl token")
                    return
                verb = body.get("verb")
                args = body.get("args") or {}
                try:
                    result = exporter.ctl.handle(verb, args)
                except KeyError:
                    self._send(400, f"unknown verb {verb!r}".encode())
                    return
                except Exception as e:  # verb handlers are user code
                    self._send(500, f"{type(e).__name__}: {e}".encode())
                    return
                self._send(200, _json.dumps(
                    {"ok": True, "verb": verb, "result": result}).encode(),
                    content_type="application/json")

        self._httpd = ThreadingHTTPServer((self.host, self.port), Handler)
        self.port = self._httpd.server_address[1]
        self._thread = threading.Thread(target=self._httpd.serve_forever,
                                        daemon=True)
        self._thread.start()
        return self

    @property
    def url(self) -> str:
        return f"http://{self.host}:{self.port}"

    def stop(self) -> None:
        if self._httpd is not None:
            self._httpd.shutdown()
            self._httpd.server_close()
            self._httpd = None

    def __enter__(self):
        return self.start() if self._httpd is None else self

    def __exit__(self, *exc):
        self.stop()


def _lib():
    lib = load_library("gang")
    lib.gang_server_start.restype = ctypes.c_void_p
    lib.gang_server_start.argtypes = [ctypes.c_int, ctypes.c_int, ctypes.c_int]
    lib.gang_server_start2.restype = ctypes.c_void_p
    lib.gang_server_start2.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
    ]
    lib.gang_server_start3.restype = ctypes.c_void_p
    lib.gang_server_start3.argtypes = [
        ctypes.c_int, ctypes.c_int, ctypes.c_int, ctypes.c_int,
        ctypes.c_char_p,
    ]
    lib.gang_server_run_id.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
    ]
    lib.gang_server_port.argtypes = [ctypes.c_void_p]
    lib.gang_server_resize.restype = ctypes.c_long
    lib.gang_server_resize.argtypes = [ctypes.c_void_p, ctypes.c_int]
    lib.gang_server_world_size.argtypes = [ctypes.c_void_p]
    lib.gang_server_generation.restype = ctypes.c_long
    lib.gang_server_generation.argtypes = [ctypes.c_void_p]
    lib.gang_server_failed.argtypes = [ctypes.c_void_p]
    lib.gang_server_dead_rank.argtypes = [ctypes.c_void_p]
    lib.gang_server_registered.argtypes = [ctypes.c_void_p]
    lib.gang_server_stop.argtypes = [ctypes.c_void_p]
    lib.gang_client_connect.restype = ctypes.c_void_p
    lib.gang_client_connect.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_char_p, ctypes.c_int,
    ]
    lib.gang_client_connect2.restype = ctypes.c_void_p
    lib.gang_client_connect2.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_char_p,
        ctypes.c_int, ctypes.POINTER(ctypes.c_int),
    ]
    lib.gang_client_connect3.restype = ctypes.c_void_p
    lib.gang_client_connect3.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_char_p,
        ctypes.c_int, ctypes.c_long, ctypes.POINTER(ctypes.c_int),
    ]
    lib.gang_client_connect4.restype = ctypes.c_void_p
    lib.gang_client_connect4.argtypes = [
        ctypes.c_char_p, ctypes.c_int, ctypes.c_int, ctypes.c_char_p,
        ctypes.c_int, ctypes.c_long, ctypes.c_char_p,
        ctypes.POINTER(ctypes.c_int),
    ]
    lib.gang_client_run_id.argtypes = [
        ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int,
    ]
    lib.gang_client_generation.restype = ctypes.c_long
    lib.gang_client_generation.argtypes = [ctypes.c_void_p]
    lib.gang_client_barrier.argtypes = [ctypes.c_void_p, ctypes.c_long]
    lib.gang_client_heartbeat.argtypes = [ctypes.c_void_p]
    lib.gang_client_world.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
    lib.gang_client_close.argtypes = [ctypes.c_void_p]
    return lib


class GangCoordinator:
    """Driver-side coordinator. world_size hosts must register.

    ``rejoin_grace_ms`` (default 0 = disabled, the original behavior):
    after a member is declared dead, a FRESH re-registration arriving
    within this window opens a NEW GENERATION — the failure latch
    clears, membership and barrier counts reset, and every rank must
    register again — so a supervisor-restarted gang reforms on the
    same coordinator instead of being refused with DEAD forever.
    Outside the window, re-registration stays refused (a dead gang
    must not be silently resurrected under survivors that already saw
    DEAD).

    REG/HB lines are GENERATION-TAGGED (closing the rejoin-grace race
    filed by the ft PR): clients echo the generation they joined, and
    the coordinator refuses stale tags with DEAD — so a survivor of
    the failed generation whose heartbeat socket broke cannot open
    (or sneak into) the new generation while its old-generation peers
    still hold live connections; only genuinely fresh registrations
    (supervisor-restarted ranks) reform the gang. Untagged lines from
    old clients keep the pre-tag semantics, so mixed-version gangs
    interoperate.
    """

    def __init__(self, world_size: int, port: int = 0,
                 heartbeat_timeout_ms: int = 10_000,
                 rejoin_grace_ms: int = 0,
                 run_id: Optional[str] = None):
        # ``run_id`` (None = untagged, the pre-run-id wire format —
        # raw-wire peers keep seeing "OK <ws> <gen>"): a gang-unique
        # id announced in every OK reply; workers stamp it on their
        # spans/events/heartbeats so a fleet collector can join the
        # per-rank streams. bringup_multihost mints one by default.
        # The id travels as ONE token on the space-delimited line
        # protocol (and sscanf caps it at 127 bytes): an id containing
        # whitespace would be silently split — the client would learn
        # a truncated id, claim it on its heartbeat-channel REG, and
        # be refused ERR run, surfacing as a baffling bring-up
        # failure. Refuse the malformed id HERE instead.
        if run_id is not None and (
                not run_id or len(run_id) > 120
                or not run_id.isascii() or not run_id.isprintable()
                or any(c.isspace() for c in run_id)):
            raise ValueError(
                f"run_id {run_id!r} is not line-protocol-safe: need a "
                f"non-empty printable-ASCII token without whitespace, "
                f"<= 120 chars (obs.mint_run_id() produces one)"
            )
        self._lib = _lib()
        self.run_id = run_id
        self._handle = self._lib.gang_server_start3(
            port, world_size, heartbeat_timeout_ms, rejoin_grace_ms,
            (run_id or "").encode(),
        )
        if not self._handle:
            raise RuntimeError("gang coordinator failed to start")
        self.port = self._lib.gang_server_port(self._handle)
        self.world_size = world_size
        self.rejoin_grace_ms = rejoin_grace_ms
        # Last-observed native state, snapshotted by stop() BEFORE the
        # handle is freed: callers (an elastic run's summary, a
        # supervisor's post-mortem) read .generation/.failed after the
        # run's finally-block stop, and passing the nulled handle into
        # the native calls is a use-after-free (observed segfault).
        self._final = {"failed": False, "dead_rank": -1,
                       "generation": 0, "registered": 0}

    @property
    def failed(self) -> bool:
        if not self._handle:
            return self._final["failed"]
        return bool(self._lib.gang_server_failed(self._handle))

    @property
    def dead_rank(self) -> int:
        if not self._handle:
            return self._final["dead_rank"]
        return int(self._lib.gang_server_dead_rank(self._handle))

    @property
    def generation(self) -> int:
        """Bumped once per rejoin-after-failure episode; generation 0
        is the original gang."""
        if not self._handle:
            return self._final["generation"]
        return int(self._lib.gang_server_generation(self._handle))

    @property
    def registered(self) -> int:
        if not self._handle:
            return self._final["registered"]
        return int(self._lib.gang_server_registered(self._handle))

    def resize(self, new_world_size: int) -> int:
        """Elastic world resize: a membership event with the same
        semantics as a rejoin-after-failure — the generation bumps,
        membership/barrier state clears, parked barrier waiters are
        released with an error, and every (surviving or new) rank must
        re-register fresh into the new generation. The elastic
        controller calls this when a rank exhausts its restart budget
        (shrink: the world continues without it) or a new host joins
        (grow). Returns the new generation."""
        if new_world_size < 1:
            raise ValueError(
                f"world_size must be >= 1, got {new_world_size}")
        if not self._handle:
            raise RuntimeError("cannot resize a stopped coordinator")
        gen = int(self._lib.gang_server_resize(self._handle,
                                               int(new_world_size)))
        if gen < 0:
            raise RuntimeError("gang coordinator refused the resize")
        self.world_size = int(new_world_size)
        return gen

    def stop(self):
        if self._handle:
            self._final = {
                "failed": bool(self._lib.gang_server_failed(self._handle)),
                "dead_rank": int(
                    self._lib.gang_server_dead_rank(self._handle)),
                "generation": int(
                    self._lib.gang_server_generation(self._handle)),
                "registered": int(
                    self._lib.gang_server_registered(self._handle)),
            }
            self._lib.gang_server_stop(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop()


class GangWorker:
    """Per-host client: register, barrier, heartbeat, peer table."""

    def __init__(self, host: str, port: int, rank: int, address: str,
                 timeout_ms: int = 30_000, heartbeat_interval_s: float = 2.0,
                 heartbeat_dir: Optional[str] = None, telemetry=None):
        self._lib = _lib()
        self.rank = rank
        # Rank/host-attributed liveness publishing (obs.heartbeat):
        # the native protocol is a liveness BIT; the emitter adds WHO
        # and HOW FAR (rank, host, pid, training step, last-seen ts),
        # readable by anything sharing the directory. Enabled by the
        # kwarg or the SPARKTORCH_TPU_HEARTBEAT_DIR env var.
        heartbeat_dir = heartbeat_dir or os.environ.get(HEARTBEAT_DIR_ENV)
        self.heartbeat = (
            HeartbeatEmitter(heartbeat_dir, rank, telemetry=telemetry)
            if heartbeat_dir else None
        )
        # Kept for heartbeat-socket reconnection (re-REG overwrites
        # members[rank] server-side while the gang is healthy; once the
        # gang has failed the coordinator refuses with DEAD).
        self._endpoint = (host, port, address, timeout_ms)
        # Fresh registration (generation tag -1: "never joined"); the
        # OK reply tells us which generation we joined, and every
        # subsequent HB/reconnect-REG carries it — so the coordinator
        # can refuse us once the gang reforms without us. -1 after
        # connect means an old untagged coordinator (legacy lines).
        self._handle = self._lib.gang_client_connect(
            host.encode(), port, rank, address.encode(), timeout_ms
        )
        if not self._handle:
            raise GangFailure(f"rank {rank}: cannot register with {host}:{port}")
        self._generation = int(self._lib.gang_client_generation(self._handle))
        # Run-id correlation: a run-id-tagged coordinator announced
        # the gang's run_id in its OK reply. Adopt it everywhere this
        # rank publishes — telemetry events (spans included) and the
        # attributed heartbeat records — so a fleet collector can join
        # the per-rank streams into one gang timeline. None when the
        # coordinator predates the run-id protocol.
        buf = ctypes.create_string_buffer(256)
        n = self._lib.gang_client_run_id(self._handle, buf, len(buf))
        self.run_id: Optional[str] = (
            buf.value.decode() if n > 0 else None
        )
        if self.run_id:
            if self.heartbeat is not None:
                self.heartbeat.set_run_id(self.run_id)
            if telemetry is not None:
                telemetry.set_run_id(self.run_id)
        # Separate connection for heartbeats: the main connection can
        # be parked inside a blocking barrier read, and interleaving
        # HB traffic on the same socket would steal its GO line. A
        # worker without a working heartbeat channel has no failure
        # detection at all — refuse to construct rather than run blind.
        # Tagged with the generation the main channel just joined (and
        # the run id it learned): a reformed gang must not accept this
        # worker's second REG as a fresh member, and a recycled
        # endpoint serving a DIFFERENT run must refuse it.
        status = ctypes.c_int(-1)
        self._hb_handle = self._lib.gang_client_connect4(
            host.encode(), port, rank, address.encode(), timeout_ms,
            self._generation, (self.run_id or "").encode(),
            ctypes.byref(status),
        )
        if not self._hb_handle:
            self._lib.gang_client_close(self._handle)
            self._handle = None
            raise GangFailure(
                f"rank {rank}: heartbeat channel to {host}:{port} refused"
            )
        self._hb_lock = threading.Lock()
        self._hb_stop = threading.Event()
        self._hb_dead = threading.Event()
        self._hb_thread = threading.Thread(
            target=self._heartbeat_loop, args=(heartbeat_interval_s,), daemon=True
        )
        self._hb_thread.start()

    # Consecutive socket-level heartbeat failures tolerated before the
    # gang is considered lost. A DEAD reply from the coordinator (rc=1)
    # is authoritative and fires immediately; rc=-1 is a local I/O
    # error (TCP hiccup, slow coordinator) and must not kill a healthy
    # run — especially now that check_gang() polls every chunk.
    _HB_MAX_IO_FAILURES = 3

    def _heartbeat_loop(self, interval: float):
        io_failures = 0
        while not self._hb_stop.wait(interval):
            if self.heartbeat is not None:
                # Attributed liveness rides the same cadence as the
                # native liveness bit: rank/host/pid/step/ts land in
                # the shared directory every tick. Never let a full
                # disk kill the native channel that actually keeps
                # this member alive.
                try:
                    self.heartbeat.beat()
                except OSError:
                    pass
            with self._hb_lock:
                if self._hb_handle is None:
                    return
                rc = self._lib.gang_client_heartbeat(self._hb_handle)
            if rc == 0:
                io_failures = 0
            elif rc > 0:  # coordinator replied DEAD: authoritative
                self._hb_dead.set()
                return
            else:
                io_failures += 1
                if io_failures >= self._HB_MAX_IO_FAILURES:
                    self._hb_dead.set()
                    return
                # A failed fd stays failed: reconnect before retrying.
                # Dial OUTSIDE the lock (close() must never wait on a
                # connect) and with a short timeout — this is a quick
                # probe, not first registration; a failed dial just
                # spends one of the remaining strikes. A DEAD reply on
                # the re-REG is authoritative (the coordinator now
                # refuses to resurrect a slot in a failed gang): stop
                # probing and declare the gang lost immediately. The
                # re-REG carries OUR generation, so if the gang failed
                # and reformed without us during the rejoin grace
                # window, the coordinator refuses this survivor with
                # DEAD instead of letting its fresh-looking REG open
                # (or join) a generation its peers aren't in — the
                # rejoin-grace race the generation tags exist to close.
                host, port, address, timeout_ms = self._endpoint
                status = ctypes.c_int(-1)
                fresh = self._lib.gang_client_connect4(
                    host.encode(), port, self.rank,
                    address.encode(), min(timeout_ms, 2000),
                    self._generation, (self.run_id or "").encode(),
                    ctypes.byref(status),
                ) or None
                if status.value == 1:
                    self._hb_dead.set()
                    return
                with self._hb_lock:
                    if self._hb_handle is None:  # close()d meanwhile
                        if fresh:
                            self._lib.gang_client_close(fresh)
                        return
                    if fresh:
                        self._lib.gang_client_close(self._hb_handle)
                        self._hb_handle = fresh

    def barrier(self, epoch: int) -> None:
        """Gang entry point — the analog of all barrier tasks reaching
        the stage (``distributed.py:39-43``). Raises on gang failure."""
        if self._hb_dead.is_set():
            raise GangFailure("gang member declared dead")
        rc = self._lib.gang_client_barrier(self._handle, epoch)
        if rc != 0:
            raise GangFailure(f"barrier {epoch} failed (rc={rc})")

    @property
    def failed(self) -> bool:
        """True once the coordinator has declared ANY member dead (the
        heartbeat reply flips to DEAD gang-wide, so survivors learn of
        a peer's death within one heartbeat interval)."""
        return self._hb_dead.is_set()

    @property
    def generation(self) -> int:
        """The gang generation this worker registered into (see
        :class:`GangCoordinator`); -1 when the coordinator predates
        the generation-tagged protocol."""
        return self._generation

    def check(self) -> None:
        """Raise :class:`GangFailure` if the gang has failed. Cheap
        (reads a local event set by the heartbeat thread) — call it
        from host-side training loops between compiled steps so a dead
        host aborts the survivors promptly instead of letting them
        wedge in the next XLA collective."""
        if self.failed:
            raise GangFailure(
                f"rank {self.rank}: gang failed (peer declared dead)"
            )

    def world(self) -> List[str]:
        buf = ctypes.create_string_buffer(1 << 16)
        n = self._lib.gang_client_world(self._handle, buf, len(buf))
        if n < 0:
            raise GangFailure("world query failed")
        return buf.value.decode().split(",") if buf.value else []

    def suspend_heartbeat(self):
        """Test hook: silence this member so the coordinator's failure
        detector fires."""
        self._hb_stop.set()

    @property
    def closed(self) -> bool:
        return self._handle is None

    def close(self):
        self._hb_stop.set()
        if self.heartbeat is not None:
            # Join the heartbeat thread BEFORE the final beat: a tick
            # already past its stop-check would otherwise publish
            # alive=True after (and over) the alive=False record.
            self._hb_thread.join(timeout=5.0)
            # Final alive=False beat: a CLEAN shutdown is readable in
            # the heartbeat table, distinct from a silent death whose
            # last record just ages with alive=True.
            self.heartbeat.close()
        with self._hb_lock:
            if self._hb_handle:
                self._lib.gang_client_close(self._hb_handle)
                self._hb_handle = None
        if self._handle:
            self._lib.gang_client_close(self._handle)
            self._handle = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
