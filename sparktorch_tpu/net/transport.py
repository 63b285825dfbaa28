"""Persistent binary-wire client for the hogwild parameter server.

The reference's client (``hogwild.py:31-62``) opens a FRESH TCP
connection per call and ships dill both ways — on the hot loop that
is a connect + slow-start + pickle round-trip per iteration.
:class:`BinaryTransport` replaces all three:

- **keep-alive**: one ``http.client.HTTPConnection`` per worker,
  reused across pulls/pushes (the server speaks HTTP/1.1); a dropped
  connection is redialed with exponential backoff.
- **binary frames** (:mod:`sparktorch_tpu.net.wire`): pushes scatter-
  write the gradient arrays' own memory onto the socket (no pickle,
  no join); pulls decode ``np.frombuffer`` views of the body.
- **version-tagged pulls**: ``X-Have-Version`` + the server's 304
  reply mean an up-to-date worker's pull is a header exchange, never
  a parameter transfer.
- **quantized pushes** with client-side error feedback: ``bf16``
  (default — gradients tolerate the 8-bit mantissa, bytes halve) or
  ``int8`` (4x, DGC-style residual feedback keeps the trajectory
  unbiased).

The interface matches ``train.hogwild``'s transport contract
(``pull`` / ``push`` / ``post_loss`` / ``alive`` / ``stats``), so
worker loops can't tell the wires apart — only the clock can.
"""

from __future__ import annotations

import contextlib
import http.client
import json
import time
from typing import Any, Dict, Optional, Tuple
from urllib.parse import urlsplit

import numpy as np

from sparktorch_tpu.ft import chaos as _chaos
from sparktorch_tpu.net import wire

_TIMEOUT = 10.0        # hogwild.py:34-38 parity for push/poll
_PULL_TIMEOUT = 180.0  # full-snapshot pulls get the generous deadline
                       # (see train/hogwild.py:_HTTP_PULL_TIMEOUT)
# Total wall-clock cap on one request's reconnect loop. Without it, a
# DEAD server costs retries x the per-request timeout (3 x 180s on the
# pull path) before the worker learns anything. Must exceed ONE pull
# timeout — the deadline is only checked between attempts, never
# mid-request, so a healthy slow pull is never killed by it.
_RECONNECT_DEADLINE = 240.0


def _new_phase_stats() -> dict:
    """Same accounting dict as ``train.hogwild._new_phase_stats`` —
    duplicated here (not imported) so net/ never imports train/."""
    return {
        "pull_s": 0.0, "pull_bytes": 0, "pulls": 0, "pull_fresh": 0,
        "push_wire_s": 0.0, "push_materialize_s": 0.0,
        "push_bytes": 0, "pushes": 0,
        "poll_s": 0.0,
        "reconnects": 0,  # redials after a connection-level failure
    }


class TransportError(RuntimeError):
    """The server answered with an unexpected status, or stayed
    unreachable through every retry."""


class BinaryTransport:
    """Zero-copy binary client for one hogwild worker.

    Not thread-safe by design: each worker owns its transport (and
    therefore its connection and its error-feedback residuals), like
    the dill ``HttpTransport`` before it.
    """

    def __init__(self, url: str, quant: Optional[str] = "bf16",
                 error_feedback: bool = True,
                 timeout: float = _TIMEOUT,
                 pull_timeout: float = _PULL_TIMEOUT,
                 retries: int = 3, backoff_s: float = 0.05,
                 deadline_s: Optional[float] = _RECONNECT_DEADLINE,
                 telemetry=None, run_id: Optional[str] = None,
                 residuals: Optional[Dict[Tuple[str, ...],
                                          np.ndarray]] = None):
        parts = urlsplit(url if "//" in url else f"http://{url}")
        if parts.scheme not in ("", "http"):
            raise ValueError(f"BinaryTransport speaks http only, got {url!r}")
        self.host = parts.hostname or "127.0.0.1"
        self.port = parts.port or 80
        if quant not in (None, "bf16", "int8"):
            raise ValueError(f"quant {quant!r}; use None, 'bf16' or 'int8'")
        self.quant = quant
        # Error-feedback residuals, path -> np.ndarray. bf16's residual
        # is small but free to track; int8 genuinely needs it.
        # ``residuals`` lets an owner inject a SHARED path-keyed store:
        # the sharded fan-out keys residuals by leaf path at the
        # ShardedTransport level, so a leaf that migrates between
        # shards on add/drain keeps its accumulated noise instead of
        # orphaning it in the old shard's transport.
        self._residuals: Optional[Dict[Tuple[str, ...], np.ndarray]] = (
            residuals if residuals is not None
            else ({} if (error_feedback and quant is not None) else None)
        )
        self.timeout = timeout
        self.pull_timeout = pull_timeout
        self.retries = max(1, retries)
        self.backoff_s = backoff_s
        # Reconnect-loop wall-clock cap: a dead server fails fast with
        # a clear error instead of spending retries x request-timeout.
        # None = uncapped (the pre-deadline behavior).
        self.deadline_s = deadline_s
        self.telemetry = telemetry
        # Run-ID correlation (16-bit tag in the frame header's reserved
        # bytes): every push this worker sends names its gang run, and
        # a pulled frame carrying a DIFFERENT nonzero tag — a worker
        # pointed at another run's server — is counted and warned, not
        # silently trained on.
        from sparktorch_tpu.obs.collector import run_tag as _rt

        self.run_tag = _rt(run_id)
        self.stats = _new_phase_stats()
        self._conn: Optional[http.client.HTTPConnection] = None

    def _rpct(self):
        """The rpctrace module, imported lazily (net/ stays importable
        without dragging the obs package in at module load) and cached
        per transport."""
        mod = getattr(self, "_rpctrace_mod", None)
        if mod is None:
            from sparktorch_tpu.obs import rpctrace as mod

            self._rpctrace_mod = mod
        return mod

    def _tracer(self):
        """This transport's tracer, resolved ONCE: the bus is fixed
        for the transport's life, and re-resolving through the global
        registry's lock per request would put a process-wide lock hop
        on the exact hot path the overhead gate bounds."""
        tracer = getattr(self, "_tracer_cached", None)
        if tracer is None:
            tracer = self._tracer_cached = self._rpct().tracer_for(
                self.telemetry)
        return tracer

    @contextlib.contextmanager
    def _trace_root(self, name: str, trace):
        """Yield the span context this request propagates: the
        caller's, when one was handed down (a ShardedTransport owns
        the per-shard hop span and this transport only propagates),
        else a freshly minted ROOT — a worker-side push/pull against a
        single server is itself the request."""
        if trace is not None:
            yield trace
            return
        with self._tracer().root_span(name, kind="client",
                                      host=self.host,
                                      port=self.port) as sp:
            yield sp.ctx

    def _trace_header(self, headers: Dict[str, str], ctx) -> Dict[str, str]:
        """Inject ``X-Trace-Context`` for sampled requests (head-based
        sampling: unsampled requests must cost the server nothing)."""
        if ctx is not None and ctx.sampled:
            headers[self._rpct().TRACE_HEADER] = ctx.to_header()
        return headers

    def _count_reconnect(self) -> None:
        self.stats["reconnects"] = self.stats.get("reconnects", 0) + 1
        tele = self.telemetry
        if tele is None:
            from sparktorch_tpu.obs import get_telemetry

            tele = self.telemetry = get_telemetry()
        tele.counter("transport_reconnects_total",
                     labels={"host": self.host, "port": self.port})

    # -- connection management --------------------------------------------

    def _connection(self, timeout: float) -> http.client.HTTPConnection:
        if self._conn is None:
            self._conn = http.client.HTTPConnection(
                self.host, self.port, timeout=timeout
            )
        else:
            # Reuse the kept-alive socket; only the deadline changes.
            self._conn.timeout = timeout
            if self._conn.sock is not None:
                self._conn.sock.settimeout(timeout)
        return self._conn

    def _drop_connection(self) -> None:
        if self._conn is not None:
            try:
                self._conn.close()
            except OSError:
                pass
            self._conn = None

    def close(self) -> None:
        self._drop_connection()

    def _request(self, method: str, path: str, body=None,
                 headers=None,
                 timeout: float = _TIMEOUT,
                 retry_on_timeout: bool = False
                 ) -> Tuple[int, bytes, Dict[str, str]]:
        """One request over the persistent connection, with reconnect +
        exponential backoff on connection-level failures. Returns
        ``(status, body, reply_headers)``.

        ``headers`` may be a dict or a CALLABLE re-evaluated on every
        attempt: a retried pull must re-read its live version state at
        send time, not replay the value captured before the first
        attempt — between a failed send and its reconnect the client's
        merged state can advance, and replaying the stale
        ``X-Have-Version`` would make the server re-ship (or worse,
        304-skip) tensors the client already holds.

        Timeouts retry only when the caller marks the request
        IDEMPOTENT (pulls/polls): a timed-out POST may have completed
        server-side, and re-sending would double-apply a gradient.
        A connection REFUSED/RESET before the response, by contrast,
        is always safe to retry — including the keep-alive race where
        the server closed an idle socket as we wrote to it.
        """
        retriable: tuple = (ConnectionError, http.client.HTTPException,
                            OSError)
        last: Optional[BaseException] = None
        t_start = time.monotonic()
        for attempt in range(self.retries):
            if (attempt > 0 and self.deadline_s is not None
                    and time.monotonic() - t_start > self.deadline_s):
                raise TransportError(
                    f"{method} {path}: reconnect deadline "
                    f"({self.deadline_s}s) exceeded after {attempt} "
                    f"attempts — server unreachable"
                ) from last
            conn = self._connection(timeout)
            try:
                act = _chaos.fire("transport.request", method=method,
                                  path=path, attempt=attempt)
                if act and act.get("drop"):
                    # Injected connection loss: fail THIS attempt the
                    # way a server-closed keep-alive socket would, so
                    # the real reconnect+backoff path runs.
                    raise ConnectionResetError("chaos: connection dropped")
                hdrs = headers() if callable(headers) else (headers or {})
                conn.request(method, path, body=body, headers=hdrs)
                resp = conn.getresponse()
                data = resp.read()  # drain so the connection is reusable
                return resp.status, data, dict(resp.headers)
            except TimeoutError as e:
                self._drop_connection()
                last = e
                if not retry_on_timeout:
                    raise
            except retriable as e:
                self._drop_connection()
                last = e
            self._count_reconnect()
            if attempt + 1 < self.retries:
                time.sleep(self.backoff_s * (2 ** attempt))
        raise TransportError(
            f"{method} {path} failed after {self.retries} attempts"
        ) from last

    # -- hogwild transport contract ---------------------------------------

    def _check_run_tag(self, body) -> None:
        frame_tag = wire.frame_run_tag(body)
        if frame_tag and self.run_tag and frame_tag != self.run_tag:
            tele = self.telemetry
            if tele is None:
                from sparktorch_tpu.obs import get_telemetry

                tele = self.telemetry = get_telemetry()
            tele.counter("transport_run_tag_mismatches_total",
                         labels={"host": self.host, "port": self.port})

    def pull(self, have_version, _trace=None):
        """``(version, params)`` newer than ``have_version``, or None
        when the server's snapshot is not newer (its 304 reply — the
        ETag-style exchange that costs ~100 header bytes, not a model).

        ``have_version`` may be a CALLABLE returning the live value:
        it is re-read on every reconnect attempt (see ``_request``).
        ``_trace`` (a sampled SpanContext) propagates a caller-owned
        request trace instead of minting a root here."""
        st = self.stats
        with self._trace_root("pull", _trace) as tctx:
            t0 = time.perf_counter()
            status, body, _ = self._request(
                "GET", "/parameters.bin",
                headers=lambda: self._trace_header(
                    {"X-Have-Version": str(int(
                        have_version() if callable(have_version)
                        else have_version
                    ))}, tctx),
                timeout=self.pull_timeout, retry_on_timeout=True,
            )
            st["pull_s"] += time.perf_counter() - t0
            st["pulls"] += 1
            if status == 304:
                return None
            if status != 200:
                raise TransportError(f"/parameters.bin -> {status}")
            st["pull_fresh"] += 1
            st["pull_bytes"] += len(body)
            self._check_run_tag(body)
            version, tree = wire.decode(body)
            return version, tree

    def pull_delta(self, have_version,
                   quant: Optional[str] = None,
                   _trace=None) -> Dict[str, Any]:
        """Per-tensor delta pull from the fleet's ``/delta.bin`` route.

        ``have_version`` (int or callable, re-read per reconnect
        attempt) is the client's last version FROM THIS SERVER; the
        reply carries only leaves whose per-tensor version advanced.
        ``quant='int8'`` asks the server for int8 leaves with
        server-side error feedback (the reply dequantizes here).

        Returns a dict: ``fresh`` (False on 304), ``version``,
        ``leaves`` (``{path: array}``), ``leaf_versions``, ``nbytes``,
        plus the resync metadata every reply carries — ``epoch`` (the
        server slot's boot nonce; a change means the server state was
        rebuilt and the client must re-pull from -1) and
        ``ring_version`` (bumped on shard add/drain; a change means
        refresh the shard map).
        """
        st = self.stats
        with self._trace_root("pull", _trace) as tctx:
            t0 = time.perf_counter()

            def _headers() -> Dict[str, str]:
                hv = have_version() if callable(have_version) \
                    else have_version
                h = {"X-Have-Version": str(int(hv))}
                if quant:
                    h["X-Pull-Quant"] = quant
                return self._trace_header(h, tctx)

            status, body, rhdrs = self._request(
                "GET", "/delta.bin", headers=_headers,
                timeout=self.pull_timeout, retry_on_timeout=True,
            )
            st["pull_s"] += time.perf_counter() - t0
            st["pulls"] += 1
            out: Dict[str, Any] = {
                "fresh": False, "version": None, "leaves": {},
                "leaf_versions": {}, "nbytes": 0,
                "epoch": _int_header(rhdrs, "X-Slot-Epoch"),
                "ring_version": _int_header(rhdrs, "X-Ring-Version"),
            }
            if status == 304:
                return out
            if status != 200:
                raise TransportError(f"/delta.bin -> {status}")
            st["pull_fresh"] += 1
            st["pull_bytes"] += len(body)
            self._check_run_tag(body)
            version, leaves, leaf_versions = wire.decode_delta(body)
            out.update(fresh=True, version=version, leaves=leaves,
                       leaf_versions=leaf_versions, nbytes=len(body))
            return out

    def fetch_json(self, path: str, timeout: Optional[float] = None) -> Any:
        """GET + parse a small JSON control route (``/fleet.json``)
        over the SAME keep-alive connection and retry discipline as
        the data wire."""
        status, body, _ = self._request(
            "GET", path, timeout=timeout or self.timeout,
            retry_on_timeout=True,
        )
        if status != 200:
            raise TransportError(f"{path} -> {status}")
        try:
            return json.loads(body)
        except ValueError as e:
            raise TransportError(f"{path}: invalid JSON: {e}") from e

    def push(self, grads, _trace=None) -> None:
        """Encode (optionally quantize with error feedback) and POST
        the gradient tree. The materialize fence is timed apart from
        the wire, matching the dill transport's honest accounting.
        A sampled trace context (minted here, or handed down via
        ``_trace``) rides the frame's header extension, with the
        ENCODE (materialize+quantize+frame) and SOCKET halves
        attributed as separate child spans."""
        st = self.stats
        tracer = self._tracer()
        with self._trace_root("push", _trace) as tctx:
            t0 = time.perf_counter()
            # np.asarray FENCES the device: the gradient compute drains
            # here, so this term is compute+download, and the request
            # below is pure wire + server apply.
            with tracer.child_span("encode", tctx, kind="internal") as _sp:
                host = _tree_to_host(grads)
                if self.quant is not None:
                    leaves, _ = wire.quantize_tree(host, self.quant,
                                                   self._residuals)
                else:
                    leaves = wire.flatten_tree(host)
                buffers = wire.encode(leaves, run_tag=self.run_tag,
                                      trace=tctx)
            nbytes = wire.frame_nbytes(buffers)
            t1 = time.perf_counter()
            st["push_materialize_s"] += t1 - t0
            # The buffer LIST (not an iterator): http.client scatter-
            # sends each part, and a connection-level retry can
            # re-iterate it — an exhausted iterator would under-send
            # the declared length.
            with tracer.child_span("socket", tctx, kind="internal",
                                   host=self.host, port=self.port):
                status, _, _ = self._request(
                    "POST", "/update.bin", body=buffers,
                    headers={"Content-Length": str(nbytes),
                             "Content-Type": wire.CONTENT_TYPE},
                    timeout=self.timeout,
                )
            if status != 200:
                raise TransportError(f"/update.bin -> {status}")
            st["push_wire_s"] += time.perf_counter() - t1
            st["push_bytes"] += nbytes
            st["pushes"] += 1

    def post_loss(self, loss: float) -> bool:
        """Early-stop vote; JSON (the one non-tensor exchange — tiny,
        and keeping it readable beats keeping it binary)."""
        t0 = time.perf_counter()
        payload = json.dumps({"loss": float(loss)}).encode()
        status, body, _ = self._request(
            "POST", "/losses.json", body=payload,
            headers={"Content-Type": "application/json"},
            timeout=self.timeout,
        )
        if status != 200:
            raise TransportError(f"/losses.json -> {status}")
        self.stats["poll_s"] += time.perf_counter() - t0
        return bool(json.loads(body)["stop"])

    def alive(self) -> bool:
        status, _, _ = self._request("GET", "/", timeout=self.timeout,
                                     retry_on_timeout=True)
        return status == 200


def _int_header(headers: Dict[str, str], name: str) -> Optional[int]:
    """Parse an int reply header; None when absent or garbled (an old
    server that doesn't send it must read as 'unknown', not 0)."""
    raw = headers.get(name)
    if raw is None:
        return None
    try:
        return int(raw)
    except ValueError:
        return None


def _tree_to_host(tree: Any):
    """Materialize device arrays to host numpy, preserving structure.
    Kept jax-optional: plain numpy trees pass through without
    importing jax (the wire codec runs device-free)."""
    try:
        import jax

        return jax.tree.map(lambda a: np.asarray(a), tree)
    except ImportError:  # pragma: no cover - jax always present in-repo
        if isinstance(tree, dict):
            return {k: _tree_to_host(v) for k, v in tree.items()}
        return np.asarray(tree)
