"""Client-side scatter/gather over a sharded parameter-server fleet.

The single hogwild server caps aggregate pull bandwidth at one socket
loop no matter how many chips train — the exact bottleneck the
reference never fixed (one Flask process on the driver,
``server.py:33-149``). The production shape is Li et al.'s
parameter-server fleet (OSDI '14): the tensor tree hash-partitioned
across N server shards, every worker talking to all of them. This
module is the CLIENT half:

- :class:`HashRing` — consistent hashing over leaf paths (md5 points,
  virtual nodes), shared verbatim by the server fleet
  (:mod:`sparktorch_tpu.serve.fleet`) so both sides compute the same
  owner for every tensor. Adding or draining a shard remaps only
  ~1/N of the keys, never the whole tree — that is what makes LIVE
  resharding possible.
- :class:`ShardedTransport` — the hogwild transport contract
  (``pull`` / ``push`` / ``post_loss`` / ``alive`` / ``stats``) over
  one :class:`~sparktorch_tpu.net.transport.BinaryTransport` per
  shard. Pulls fan out as per-tensor DELTA requests (``/delta.bin``:
  only leaves whose version advanced ship; optional int8 payloads
  with server-side error feedback) and reassemble into the full tree
  from a client-side leaf cache; pushes split the gradient tree by
  ring ownership and scatter in parallel.
- Fault degradation: a shard that stops answering degrades the
  transport (its leaves freeze at the cached values, its gradient
  partials are dropped and counted) for a GRACE WINDOW; only a shard
  dead past the grace fails the worker. The fleet's monitor restarts
  a dead shard frontend well inside the default grace, so a seeded
  shard kill costs some staleness, not the run.
- Topology refresh: every delta reply carries ``X-Ring-Version``; a
  mismatch against the client's ring triggers a re-fetch of
  ``/fleet.json`` (any shard serves it), so workers learn about
  add/drain within one pull — no control channel needed.
"""

from __future__ import annotations

import bisect
import hashlib
import threading
import time
from concurrent.futures import ThreadPoolExecutor
from typing import Any, Dict, List, Mapping, Optional, Tuple

import numpy as np

from sparktorch_tpu.net import wire
from sparktorch_tpu.net.transport import (
    BinaryTransport,
    TransportError,
    _new_phase_stats,
    _tree_to_host,
)

Path = Tuple[str, ...]

_RING_REPLICAS = 64  # virtual nodes per shard: evens out md5 arcs


def _hash64(token: str) -> int:
    return int.from_bytes(hashlib.md5(token.encode()).digest()[:8], "big")


class HashRing:
    """Consistent hashing of leaf paths onto shard ids.

    Deterministic across processes (md5, not the salted builtin
    ``hash``), so a server fleet and every remote client agree on
    ownership from the shard-id list alone. ``replicas`` virtual
    points per shard keep the arcs even; add/remove moves only the
    keys on the changed arcs (~1/N of the space).
    """

    def __init__(self, shard_ids=(), replicas: int = _RING_REPLICAS):
        self.replicas = int(replicas)
        self._points: List[Tuple[int, str]] = []  # sorted (hash, sid)
        self._ids: List[str] = []
        for sid in shard_ids:
            self.add(sid)

    def add(self, shard_id) -> None:
        sid = str(shard_id)
        if sid in self._ids:
            raise ValueError(f"shard {sid!r} already on the ring")
        self._ids.append(sid)
        for i in range(self.replicas):
            bisect.insort(self._points, (_hash64(f"{sid}#{i}"), sid))

    def remove(self, shard_id) -> None:
        sid = str(shard_id)
        if sid not in self._ids:
            raise ValueError(f"shard {sid!r} not on the ring")
        self._ids.remove(sid)
        self._points = [p for p in self._points if p[1] != sid]

    @property
    def shard_ids(self) -> List[str]:
        return list(self._ids)

    def owner(self, path: Path) -> str:
        """The shard owning ``path`` (first ring point clockwise of
        the key's hash)."""
        if not self._points:
            raise ValueError("empty ring")
        h = _hash64("/".join(path))
        i = bisect.bisect_right(self._points, (h, "￿"))
        if i == len(self._points):
            i = 0
        return self._points[i][1]

    def assignment(self, paths) -> Dict[str, List[Path]]:
        """``{shard_id: [paths]}`` — every shard present, even when
        empty (a fresh shard owns no keys until one hashes to it)."""
        out: Dict[str, List[Path]] = {sid: [] for sid in self._ids}
        for path in paths:
            out[self.owner(tuple(path))].append(tuple(path))
        return out


class StaticFleetView:
    """A fixed shard map for clients of a fleet that never reshapes
    (tests, single-host rigs)."""

    def __init__(self, shards: Mapping[Any, str],
                 replicas: int = _RING_REPLICAS):
        self._doc = {
            "ring_version": 1,
            "replicas": int(replicas),
            "shards": {str(s): url for s, url in shards.items()},
        }

    def describe(self) -> Dict[str, Any]:
        return self._doc


class HttpFleetView:
    """Fleet topology fetched from any shard's (or the gateway's)
    ``/fleet.json`` — the remote-worker discovery path."""

    def __init__(self, url: str, timeout: float = 5.0):
        self._transport = BinaryTransport(url, quant=None, timeout=timeout)

    def describe(self) -> Dict[str, Any]:
        return self._transport.fetch_json("/fleet.json")

    def close(self) -> None:
        self._transport.close()


class _ShardClient:
    __slots__ = ("sid", "transport", "have", "epoch", "first_fail",
                 "synced")

    def __init__(self, sid: str, transport: BinaryTransport):
        self.sid = sid
        self.transport = transport
        self.have = -1                 # last version pulled from this shard
        self.epoch: Optional[int] = None  # slot boot nonce last seen
        self.first_fail: Optional[float] = None  # degrade-window start
        # True once this shard's leaves have merged into the cache at
        # least once. NOT derivable from `have` — an epoch resync
        # resets have to -1 while the cache stays fully populated.
        self.synced = False


class ShardedTransport:
    """Scatter/gather hogwild transport over a param-server fleet.

    Worker-owned like :class:`BinaryTransport` (per-worker
    connections, residuals, and leaf cache); the internal fan-out
    threads touch disjoint shards (and disjoint leaf-cache keys), so
    the tensor path is lock-free — only the shared stats counters
    take a lock.

    ``fleet`` is anything with ``describe() ->`` the ``/fleet.json``
    document (a :class:`~sparktorch_tpu.serve.fleet.ParamServerFleet`
    in-process, an :class:`HttpFleetView` remotely, or a
    :class:`StaticFleetView`). ``quant`` compresses pushes (bf16
    default / int8+EF); ``pull_quant='int8'`` asks the fleet for int8
    DELTA pulls with server-side error feedback — halving the
    dominant pull direction again on top of the delta savings.
    ``grace_s`` bounds how long a dead shard degrades the gang before
    it fails the worker.
    """

    def __init__(self, fleet, quant: Optional[str] = "bf16",
                 pull_quant: Optional[str] = None,
                 error_feedback: bool = True,
                 grace_s: float = 30.0,
                 parallel_fan: Optional[bool] = None,
                 telemetry=None, run_id: Optional[str] = None,
                 **transport_kwargs):
        if pull_quant not in (None, "int8"):
            raise ValueError(f"pull_quant {pull_quant!r}; use None or 'int8'")
        self._fleet = fleet
        self.quant = quant
        self.pull_quant = pull_quant
        self.error_feedback = error_feedback
        self.grace_s = float(grace_s)
        # Fan-out strategy: thread-parallel requests only pay off when
        # the per-shard wire wait dominates (remote shards, big
        # fleets) — on a local fleet the executor's wakeup latency
        # under a busy GIL COSTS more than the overlapped RTTs save
        # (measured: sequential fan halves swarm p99 on loopback).
        # None = auto by fleet size at request time.
        self.parallel_fan = parallel_fan
        self.telemetry = telemetry
        self.run_id = run_id
        # Dead-shard probes must fail INSIDE the grace window, not
        # after the single-server wire's generous defaults — and that
        # includes the per-attempt socket timeouts: the reconnect
        # deadline is only checked BETWEEN attempts, so a wedged shard
        # (connection accepted, no reply) is bounded by pull_timeout,
        # not deadline_s. Keep deadline_s > pull_timeout (the
        # transport's documented invariant: a healthy slow pull is
        # never killed mid-request by the deadline). Deltas are small;
        # a fleet serving huge frames over slow links should raise
        # grace_s (all four knobs scale with it) or override directly.
        transport_kwargs.setdefault("retries", 2)
        transport_kwargs.setdefault("pull_timeout", max(1.0, grace_s / 3))
        transport_kwargs.setdefault(
            "timeout", min(10.0, max(1.0, grace_s / 3)))
        transport_kwargs.setdefault("deadline_s", max(1.0, grace_s / 2))
        self._transport_kwargs = transport_kwargs
        self._clients: Dict[str, _ShardClient] = {}
        self._ring: Optional[HashRing] = None
        self._ring_version = -1
        # ONE push-residual store for the whole fleet, keyed by leaf
        # PATH and injected into every per-shard transport. Residuals
        # follow the leaf, not the shard: when add/drain migrates a
        # leaf to a new owner, its accumulated quantization noise
        # folds into the next push to the NEW shard instead of
        # orphaning one window's worth in the old transport. Fan-out
        # threads touch disjoint paths (ring ownership), so the dict
        # needs no lock.
        self._push_residuals: Optional[Dict[Path, np.ndarray]] = (
            {} if (error_feedback and quant is not None) else None
        )
        self._leaves: Dict[Path, np.ndarray] = {}
        self._executor: Optional[ThreadPoolExecutor] = None
        self._own = self._fresh_own()
        # Guards _own counters touched from fan-out threads (the dict
        # slots are shared even though the SHARDS are disjoint).
        self._own_lock = threading.Lock()
        self._refresh_lock = threading.Lock()
        self._refresh()

    # -- stats (the hogwild budget contract) -------------------------------

    @staticmethod
    def _fresh_own() -> dict:
        st = _new_phase_stats()
        st.update({"reconnects": 0, "shards": 0, "shard_failures": 0,
                   "pushes_skipped": 0, "delta_leaves": 0})
        return st

    @property
    def stats(self) -> dict:
        """Aggregated view: fan-out WALL times measured here (summing
        the per-shard walls would overstate parallel time), byte and
        reconnect counters summed from the per-shard transports."""
        out = dict(self._own)
        out["shards"] = len(self._clients)
        for c in self._clients.values():
            ct = c.transport.stats
            out["pull_bytes"] += ct.get("pull_bytes", 0)
            out["push_bytes"] += ct.get("push_bytes", 0)
            out["reconnects"] += ct.get("reconnects", 0)
        return out

    @stats.setter
    def stats(self, value) -> None:
        # The worker loop installs a fresh dict per round; reset the
        # per-shard transports too so bytes aren't double-counted.
        self._own = self._fresh_own()
        for c in self._clients.values():
            c.transport.stats = _new_phase_stats()

    # -- topology ----------------------------------------------------------

    def _refresh(self) -> None:
        """(Re)build the ring + per-shard clients from the fleet's
        topology document. Existing clients (and their connections,
        residuals, have-versions) survive; removed shards close."""
        with self._refresh_lock:
            doc = self._fleet.describe()
            version = int(doc.get("ring_version", 0))
            if version == self._ring_version and self._clients:
                return
            shards: Dict[str, str] = {
                str(s): u for s, u in (doc.get("shards") or {}).items()
            }
            ring = HashRing(replicas=int(doc.get("replicas",
                                                 _RING_REPLICAS)))
            for sid in shards:
                ring.add(sid)
            for sid in list(self._clients):
                if sid not in shards:
                    self._clients.pop(sid).transport.close()
            for sid, url in shards.items():
                if sid not in self._clients:
                    self._clients[sid] = _ShardClient(
                        sid,
                        BinaryTransport(
                            url, quant=self.quant,
                            error_feedback=self.error_feedback,
                            telemetry=self.telemetry, run_id=self.run_id,
                            residuals=self._push_residuals,
                            **self._transport_kwargs,
                        ),
                    )
            self._ring = ring
            self._ring_version = version
            if self._executor is not None:
                self._executor.shutdown(wait=False)
                self._executor = None

    def _pool(self) -> ThreadPoolExecutor:
        if self._executor is None:
            self._executor = ThreadPoolExecutor(
                max_workers=max(1, len(self._clients)),
                thread_name_prefix="sharded-transport",
            )
        return self._executor

    def _fan(self, fn, items: list) -> list:
        """Apply ``fn`` across shards: thread-parallel for big/remote
        fleets, sequential over the keep-alive connections otherwise
        (see ``parallel_fan``)."""
        parallel = (self.parallel_fan if self.parallel_fan is not None
                    else len(items) > 4)
        if parallel and len(items) > 1:
            return list(self._pool().map(fn, items))
        return [fn(item) for item in items]

    def _count(self, name: str, labels: Optional[dict] = None) -> None:
        if self.telemetry is not None:
            self.telemetry.counter(name, labels=labels or {})

    def _tracer(self):
        """This transport's rpc tracer (the worker bus's), resolved
        once — re-resolving through the registry's global lock per
        shard hop would make fan-out threads contend on it. The
        sharded fan-out owns the REQUEST root: one ``pull``/``push``
        root span per operation, one ``shard_*`` child per shard hop,
        with the per-shard BinaryTransports only propagating."""
        tracer = getattr(self, "_tracer_cached", None)
        if tracer is None:
            from sparktorch_tpu.obs.rpctrace import tracer_for

            tracer = self._tracer_cached = tracer_for(self.telemetry)
        return tracer

    # -- fault degradation -------------------------------------------------

    def _degrade(self, client: _ShardClient, exc: BaseException,
                 op: str) -> None:
        """A shard failed one operation: degrade (freeze its leaves /
        drop its partial) inside the grace window, fail the worker
        beyond it. Counted either way — silent brown-outs are how
        sharded systems rot."""
        now = time.monotonic()
        if client.first_fail is None:
            client.first_fail = now
        with self._own_lock:
            self._own["shard_failures"] += 1
        self._count("sharded_shard_failures_total",
                    {"shard": client.sid, "op": op})
        if now - client.first_fail > self.grace_s:
            raise TransportError(
                f"shard {client.sid} dead past the {self.grace_s}s grace "
                f"window ({op})"
            ) from exc

    # -- hogwild transport contract ----------------------------------------

    def pull(self, have_version: int):
        """Fan a delta pull across every shard, merge the advanced
        leaves into the cached tree, and return ``(version, tree)``
        when anything moved — None when every shard said 304. The
        composite version is the sum of shard versions (what the
        worker hands back; the real freshness state is per-shard)."""
        st = self._own
        t0 = time.perf_counter()
        clients = list(self._clients.values())
        with self._tracer().root_span("pull", kind="client",
                                      shards=len(clients)) as root:
            results = self._fan(
                lambda c: self._pull_shard(c, root.ctx), clients)
        st["pull_s"] += time.perf_counter() - t0
        st["pulls"] += 1
        fresh = any(r and r.get("fresh") for r in results)
        ring_versions = [r["ring_version"] for r in results
                         if r and r.get("ring_version") is not None]
        if ring_versions and max(ring_versions) > self._ring_version:
            self._refresh()
        version = sum(c.have for c in self._clients.values() if c.have > 0)
        if not fresh:
            # A from-scratch caller (have_version < 0: a supervisor-
            # RESTARTED worker reusing this transport, or a new round)
            # must get parameters even when every shard said 304 — the
            # cached assembled tree IS the current state as of this
            # sweep. Without this, a restarted worker's first pull
            # returns None and it trains on params=None.
            if (not callable(have_version) and int(have_version) < 0
                    and self._leaves):
                st["pull_fresh"] += 1
                return version, wire.unflatten_tree(
                    list(self._leaves.items()))
            return None
        st["pull_fresh"] += 1
        return version, wire.unflatten_tree(list(self._leaves.items()))

    def _pull_shard(self, client: _ShardClient,
                    trace_parent=None) -> Optional[dict]:
        # Client-observed per-shard hop latency, as a HISTOGRAM: this
        # is where a straggling shard actually shows (server-side
        # wire_latency_s times the handler, not the wire — a
        # network/queueing delay lands here and only here), which
        # makes it the series the collector's hot-shard alert rules
        # watch for sustained p99 breaches.
        hop_t0 = time.perf_counter()
        try:
            return self._pull_shard_inner(client, trace_parent)
        finally:
            if self.telemetry is not None:
                self.telemetry.observe("sharded.shard_pull_latency_s",
                                       time.perf_counter() - hop_t0,
                                       labels={"shard": client.sid})

    def _pull_shard_inner(self, client: _ShardClient,
                          trace_parent=None) -> Optional[dict]:
        with self._tracer().child_span("shard_pull", trace_parent,
                                       kind="client",
                                       shard=client.sid) as tsp:
            # tsp.ctx when this hop records; else the ROOT's context
            # (possibly the shared unsampled one) so the per-shard
            # transport propagates the root's sampling decision
            # instead of minting an independent root per shard — a
            # 99%-unsampled sharded pull must not fill the ring with
            # shard-level "requests" (or trip the SLO hatch per hop).
            hop_ctx = tsp.ctx or trace_parent
            try:
                res = client.transport.pull_delta(lambda: client.have,
                                                  quant=self.pull_quant,
                                                  _trace=hop_ctx)
                epoch = res.get("epoch")
                if (epoch is not None and client.epoch is not None
                        and epoch != client.epoch):
                    # The shard's slot was rebuilt (restart, re-add):
                    # its version counter restarted, so our
                    # have-version is meaningless — full resync from -1.
                    client.have = -1
                    self._count("sharded_epoch_resyncs_total",
                                {"shard": client.sid})
                    res = client.transport.pull_delta(
                        lambda: client.have, quant=self.pull_quant,
                        _trace=hop_ctx)
                    epoch = res.get("epoch")
                if epoch is not None:
                    client.epoch = epoch
            except (TransportError, wire.WireError, OSError) as e:
                tsp.set_error(e)
                if not client.synced:
                    # Never synced: there are no cached leaves to
                    # freeze, so "degrading" would hand the worker a
                    # PARTIAL tree (missing this shard's ~1/N of the
                    # model) and crash it inside flax instead. Fail the
                    # pull loudly; the worker (or its supervisor)
                    # retries after the monitor's restart. (A dedicated
                    # flag, not have<0: an epoch resync resets `have`
                    # while the cache stays complete — a flaky resync
                    # retry must take the grace-window path like any
                    # other mid-run failure.)
                    raise TransportError(
                        f"shard {client.sid} unreachable before its "
                        f"first sync — no cached leaves to degrade to"
                    ) from e
                self._degrade(client, e, "pull")
                # The hop stays IN the trace, closed with error status
                # and marked degraded: a grace-window brown-out must be
                # visible in the request tree, not an absent branch.
                tsp.annotate(degraded=True)
                return None
            client.first_fail = None
            if res.get("fresh"):
                client.have = int(res["version"])
                client.synced = True
                with self._own_lock:
                    self._own["delta_leaves"] += len(res["leaves"])
                # Disjoint key ranges per shard: concurrent merges from
                # the fan-out threads never write the same path.
                self._leaves.update(res["leaves"])
            return res

    def push(self, grads) -> None:
        """Split the gradient tree by ring ownership and scatter the
        partial trees to their shards in parallel. Quantization
        residuals live in ONE path-keyed store shared by every shard
        transport (see ``_push_residuals``), so error feedback stays
        exact per tensor even across a reshard that migrates the leaf
        to a different owner."""
        st = self._own
        t0 = time.perf_counter()
        host = _tree_to_host(grads)
        flat = dict(wire.flatten_tree(host))
        groups = self._ring.assignment(flat)
        t1 = time.perf_counter()
        st["push_materialize_s"] += t1 - t0

        def _push_one(item, trace_parent=None) -> None:
            sid, paths = item
            if not paths:
                return
            client = self._clients[sid]
            partial = wire.unflatten_tree([(p, flat[p]) for p in paths])
            with self._tracer().child_span("shard_push", trace_parent,
                                           kind="client",
                                           shard=sid) as tsp:
                try:
                    # Root ctx fallback like _pull_shard: an unsampled
                    # request must suppress per-shard root minting.
                    client.transport.push(partial,
                                          _trace=tsp.ctx or trace_parent)
                    client.first_fail = None
                except (TransportError, wire.WireError, OSError) as e:
                    # Hogwild tolerates a lost gradient partial the
                    # same way it tolerates staleness; a shard in its
                    # grace window costs updates, not the run.
                    tsp.set_error(e)
                    tsp.annotate(degraded=True)
                    with self._own_lock:
                        self._own["pushes_skipped"] += 1
                    self._count("sharded_pushes_skipped_total",
                                {"shard": sid})
                    self._degrade(client, e, "push")

        with self._tracer().root_span("push", kind="client",
                                      shards=len(self._clients)) as root:
            self._fan(lambda item: _push_one(item, root.ctx),
                      list(groups.items()))
        st["push_wire_s"] += time.perf_counter() - t1
        st["pushes"] += 1

    def post_loss(self, loss: float) -> bool:
        """Early-stop vote, preferring the lowest-id shard but FAILING
        OVER to the next live one — every shard shares the fleet's
        windowed stopper, so a dead vote shard in its grace window
        must not swallow loss samples (a deferred stop decision and a
        skewed window once it recovers). Returns False only when no
        shard can take the vote."""
        t0 = time.perf_counter()
        out = False
        for sid in sorted(self._clients):
            client = self._clients[sid]
            try:
                out = client.transport.post_loss(loss)
                client.first_fail = None
                break
            except (TransportError, OSError) as e:
                self._degrade(client, e, "post_loss")
        self._own["poll_s"] += time.perf_counter() - t0
        return out

    def alive(self) -> bool:
        self._refresh()
        for client in self._clients.values():
            try:
                if client.transport.alive():
                    return True
            except (TransportError, OSError):
                continue
        return False

    def close(self) -> None:
        if self._executor is not None:
            self._executor.shutdown(wait=False)
            self._executor = None
        for client in self._clients.values():
            client.transport.close()
