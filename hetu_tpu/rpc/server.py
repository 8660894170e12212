"""Cluster coordination server.

Rebuild of the reference's gRPC DeviceController service
(reference: protos/heturpc.proto:10-69 — Connect, GetRank, Commit/GetHostName,
Commit/GetDeviceInfo, Barrier, Consistent, HeartBeat, Put/Get KV, Exit,
WorkerStop; python servers rpc/heturpc_polling_server.py:17 and the elastic
variant heturpc_elastic_server.py:39 with heartbeat monitor :463).

TPU-native role: jax.distributed handles low-level multi-host bootstrap; this
service supplies what the reference layers ON TOP over DCN — a KV store,
named barriers, liveness (heartbeats + dead-worker detection), consistency
votes, and stop/relaunch signaling for the elastic trainer.  Implemented as
length-prefixed JSON over TCP (stdlib-only; the reference's proto surface,
minus protoc codegen).
"""
from __future__ import annotations

import json
import socket
import struct
import threading
import time
from typing import Any, Dict, Optional, Set

import numpy as np

from hetu_tpu.obs.aggregate import ClusterAggregator
from hetu_tpu.obs.metrics import get_registry
from hetu_tpu.rpc.wire import decode_rows, decode_telemetry, encode_rows
from hetu_tpu.utils.logging import get_logger

logger = get_logger("rpc.server")


def _send(conn: socket.socket, obj: Any):
    data = json.dumps(obj).encode()
    conn.sendall(struct.pack("<I", len(data)) + data)


def _recv(conn: socket.socket) -> Optional[Any]:
    hdr = b""
    while len(hdr) < 4:
        chunk = conn.recv(4 - len(hdr))
        if not chunk:
            return None
        hdr += chunk
    (n,) = struct.unpack("<I", hdr)
    buf = b""
    while len(buf) < n:
        chunk = conn.recv(min(65536, n - len(buf)))
        if not chunk:
            return None
        buf += chunk
    return json.loads(buf.decode())


class CoordinationServer:
    """One instance per cluster (reference: DeviceController server)."""

    def __init__(self, host: str = "127.0.0.1", port: int = 0,
                 world_size: Optional[int] = None,
                 heartbeat_timeout: float = 10.0,
                 reattach_grace: Optional[float] = None,
                 telemetry_window_s: float = 60.0):
        self.world_size = world_size
        self.heartbeat_timeout = heartbeat_timeout
        # how long a rank whose connection tore may `reattach` before it
        # is declared dead (None -> min(heartbeat_timeout, 2s)).  0 =
        # legacy behavior: any connection loss is instant worker death.
        self.reattach_grace = (min(heartbeat_timeout, 2.0)
                               if reattach_grace is None else reattach_grace)
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(128)
        self.host, self.port = self._sock.getsockname()

        self._lock = threading.Lock()
        self._next_rank = 0
        self._workers: Dict[int, Dict[str, Any]] = {}   # rank -> info
        self._kv: Dict[str, Any] = {}
        self._barriers: Dict[str, Set[int]] = {}
        self._barrier_gen: Dict[str, int] = {}
        self._votes: Dict[str, Dict[int, Any]] = {}
        self._stop_flags: Set[int] = set()
        # PS embedding tables live under their OWN lock: a large pull's
        # base64 encode must not stall heartbeats on the coordination lock
        # (the monitor would mark every worker lost mid-transfer)
        self._ps: Dict[str, np.ndarray] = {}
        self._ps_lock = threading.Lock()
        # cluster telemetry aggregation (hetu_tpu/obs/aggregate.py): folds
        # workers' telemetry_push payloads into the time-windowed
        # ClusterSnapshot.  Owns its own lock — ingest/snapshot must not
        # stall heartbeats on the coordination lock.  Idle (no pushes —
        # HETU_TPU_TELEMETRY_PUSH unset on the workers) it holds no state
        # and costs nothing.
        self.telemetry = ClusterAggregator(window_s=telemetry_window_s)
        self._shutdown = False
        self._threads = []
        self._conns = []
        self._accept_thread = threading.Thread(target=self._accept_loop,
                                               daemon=True)
        self._accept_thread.start()
        self._monitor_thread = threading.Thread(target=self._monitor_loop,
                                                daemon=True)
        self._monitor_thread.start()

    # ------------------------------------------------------------------
    def _accept_loop(self):
        while not self._shutdown:
            try:
                conn, _ = self._sock.accept()
            except OSError:
                return
            t = threading.Thread(target=self._serve_conn, args=(conn,),
                                 daemon=True)
            t.start()
            # prune finished connection threads (and their sockets) before
            # tracking the new one: long elastic runs see thousands of
            # reconnects, and append-only lists grow without bound
            live = [(x, c) for x, c in zip(self._threads, self._conns)
                    if x.is_alive()]
            self._threads = [x for x, _ in live] + [t]
            self._conns = [c for _, c in live] + [conn]

    def _monitor_loop(self):
        """Dead-worker detection (reference: elastic server HeartBeat monitor
        :463 — on loss, mark dead and signal WorkerStop to the others)."""
        sweep = min(self.heartbeat_timeout / 4,
                    max(self.reattach_grace / 2, 0.05)
                    if self.reattach_grace > 0 else float("inf"))
        while not self._shutdown:
            time.sleep(sweep)
            now = time.time()
            with self._lock:
                # sweep completed vote rounds whose collectors never returned
                # (rounds are client-versioned name#N keys, so deleting an
                # orphan cannot poison a later round)
                for vname in list(self._votes):
                    st = self._votes[vname]
                    if st.get("done_at") and now - st["done_at"] > 60.0:
                        del self._votes[vname]
                    elif st.get("done_at") is None and \
                            now - st.get("started_at", now) > 300.0:
                        # abandoned mid-vote (a member died before count
                        # was reached; clients timed out and moved to a
                        # newer round) — without this the elastic retry
                        # path leaks one entry per interrupted vote
                        del self._votes[vname]
                for rank, info in list(self._workers.items()):
                    if not info.get("alive"):
                        continue
                    if now - info["last_beat"] > self.heartbeat_timeout:
                        # stop BOTH the dead worker (if it resurrects, it must
                        # not rejoin the old mesh — split-brain guard) and the
                        # survivors so they can re-mesh
                        # (reference: WorkerStop broadcast on worker loss)
                        self._mark_lost_locked(rank, "heartbeat timeout")
                    elif info.get("conn_lost_at") is not None and \
                            now - info["conn_lost_at"] > self.reattach_grace:
                        # its connection tore and no reattach arrived
                        # within the grace window: that IS process death
                        self._mark_lost_locked(
                            rank, "connection lost (reattach grace expired)")

    # ------------------------------------------------------------------
    def _serve_conn(self, conn: socket.socket):
        # each client holds ONE persistent socket, so a broken connection is
        # STRONG evidence of process death — but reconnecting clients get a
        # short `reattach_grace` to re-attach their rank before it is
        # declared dead (far shorter than the heartbeat timeout, which can
        # false-positive when a worker's GIL is pinned inside a long XLA
        # compile).  Heartbeats stay as the backstop for network partitions
        # (reference: gRPC channel-break detection).
        state = {"rank": None, "clean": False, "gen": 0}
        try:
            with conn:
                while not self._shutdown:
                    try:
                        req = _recv(conn)
                    except OSError as e:
                        logger.debug(f"conn recv error: {e}")
                        return
                    if req is None or self._shutdown:
                        # a request that arrives after `close` is not
                        # acknowledged: the state it would change is gone
                        # with this server, and the client re-issues it
                        # to the next one (`close` cannot shut a
                        # connection the accept loop has yet to list)
                        return
                    try:
                        resp = self._handle(req, state)
                    except Exception as e:  # never die on bad input
                        logger.warning(
                            f"handler error for {req.get('op')}: {e!r}")
                        resp = {"ok": False, "error": str(e)}
                    try:
                        _send(conn, resp)
                    except OSError as e:
                        logger.warning(f"conn send error: {e}")
                        return
        finally:
            if state["rank"] is not None and not state["clean"]:
                self._conn_lost(state["rank"], state["gen"])

    def _conn_lost(self, rank: int, gen: int):
        """A worker's connection tore without a clean exit.  With a
        reattach grace window the rank gets that long to come back on a
        new socket (auto-reconnecting client); without one, this is
        instant worker death (legacy behavior)."""
        with self._lock:
            w = self._workers.get(rank)
            if w is None or not w.get("alive"):
                return
            if w.get("conn_gen", 0) != gen:
                return   # a newer connection already took over this rank
            if self.reattach_grace <= 0:
                self._mark_lost_locked(rank, "connection lost")
                return
            w["conn_lost_at"] = time.time()
            logger.info(f"worker {rank} connection lost; "
                        f"{self.reattach_grace:.1f}s reattach grace")

    def _mark_lost(self, rank: int, why: str):
        with self._lock:
            self._mark_lost_locked(rank, why)

    def broadcast_stop(self):
        """Stop-flag every alive worker (the WorkerStop broadcast, from
        the server side).  The orchestrator uses this to force a re-mesh
        when membership GROWS — replacement slots joining after a host
        loss — since growth alone does not trip the loss monitor."""
        with self._lock:
            for r, w in self._workers.items():
                if w.get("alive"):
                    self._stop_flags.add(r)
            self._kv["__membership_change__"] = time.time()

    def alive_ranks(self):
        with self._lock:
            return sorted(r for r, w in self._workers.items()
                          if w.get("alive"))

    def kv_get(self, key, default=None):
        with self._lock:
            return self._kv.get(key, default)

    def _mark_lost_locked(self, rank: int, why: str):
        info = self._workers.get(rank)
        if info is None or not info.get("alive"):
            return
        info["alive"] = False
        info.pop("conn_lost_at", None)
        reg = get_registry()
        reg.inc("rpc.workers_lost", reason=why)
        reg.set_gauge("rpc.alive_workers", sum(
            1 for w in self._workers.values() if w.get("alive")))
        logger.warning(f"worker {rank} lost ({why}); signaling stop "
                       "to survivors")
        self._kv["__membership_change__"] = time.time()
        self._stop_flags.add(rank)
        for r, w in self._workers.items():
            if w.get("alive"):
                self._stop_flags.add(r)

    def _handle(self, req: Dict[str, Any],
                conn_state: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
        op = req.get("op")
        if isinstance(op, str) and op.startswith("ps_"):
            return self._handle_ps(op, req)
        if op in ("telemetry_push", "telemetry_snapshot"):
            # the aggregator has its own lock; a fat push/snapshot must
            # not stall heartbeats on the coordination lock (same policy
            # as the PS tables)
            return self._handle_telemetry(op, req)
        with self._lock:
            if op == "connect":        # Connect + GetRank
                rank = self._next_rank
                self._next_rank += 1
                self._workers[rank] = {
                    "info": req.get("info", {}), "alive": True,
                    "last_beat": time.time(), "conn_gen": 0}
                reg = get_registry()
                reg.inc("rpc.connects")
                reg.set_gauge("rpc.alive_workers", sum(
                    1 for w in self._workers.values() if w.get("alive")))
                if conn_state is not None:
                    conn_state["rank"] = rank
                    conn_state["gen"] = 0
                return {"ok": True, "rank": rank,
                        "world_size": self.world_size}
            if op == "reattach":       # reconnecting client re-claims rank
                rank = req["rank"]
                w = self._workers.get(rank)
                if w is None:
                    # a RESTARTED server has no membership: accept the
                    # claimed rank (each client claims only the rank it
                    # held, so claims are unique) and grow _next_rank past
                    # it so fresh connects never collide
                    w = self._workers[rank] = {
                        "info": req.get("info", {}), "alive": True,
                        "last_beat": time.time(), "conn_gen": 0}
                    self._next_rank = max(self._next_rank, rank + 1)
                if not w.get("alive"):
                    # declared dead: resurrecting would re-enter the old
                    # mesh (split-brain) — the client must connect fresh
                    return {"ok": True, "accepted": False}
                w["conn_gen"] = w.get("conn_gen", 0) + 1
                w["last_beat"] = time.time()
                w.pop("conn_lost_at", None)
                if conn_state is not None:
                    conn_state["rank"] = rank
                    conn_state["gen"] = w["conn_gen"]
                get_registry().inc("rpc.reattaches")
                return {"ok": True, "accepted": True}
            if op == "heartbeat":      # HeartBeat
                rank = req["rank"]
                stop = rank in self._stop_flags
                if rank in self._workers:
                    now = time.time()
                    prev = self._workers[rank]["last_beat"]
                    self._workers[rank]["last_beat"] = now
                    # straggler visibility: per-worker inter-beat gap
                    # histogram + last-seen gauge (a worker whose gap
                    # creeps toward heartbeat_timeout is about to be
                    # declared dead — see tools_straggler.py)
                    reg = get_registry()
                    reg.observe("rpc.heartbeat_gap_s", now - prev,
                                rank=rank)
                    reg.set_gauge("rpc.worker_last_beat_t", now, rank=rank)
                    # a stop-flagged worker is NOT resurrected by a late
                    # heartbeat — it must re-connect for a fresh rank
                    if not stop:
                        self._workers[rank]["alive"] = True
                return {"ok": True, "stop": stop}
            if op == "put":            # PutJson/PutBytes...
                self._kv[req["key"]] = req["value"]
                return {"ok": True}
            if op == "get":            # GetJson (blocking handled client-side)
                key = req["key"]
                if key in self._kv:
                    return {"ok": True, "found": True, "value": self._kv[key]}
                return {"ok": True, "found": False}
            if op == "barrier":        # Barrier
                name, rank, count = req["name"], req["rank"], req["count"]
                gen = self._barrier_gen.setdefault(name, 0)
                # round pinning makes the enter idempotent: a retried or
                # duplicated enter whose round already RELEASED must not
                # leak into the next round's member set (it would release
                # that round one entrant early and hang this client)
                expect = req.get("gen_expect")
                if expect is not None and gen != expect:
                    return {"ok": True, "released": gen > expect,
                            "gen": gen}
                members = self._barriers.setdefault(name, set())
                members.add(rank)
                if len(members) >= count:
                    self._barrier_gen[name] = gen + 1
                    self._barriers[name] = set()
                    return {"ok": True, "released": True, "gen": gen + 1}
                return {"ok": True, "released": False, "gen": gen}
            if op == "barrier_poll":
                name, gen = req["name"], req["gen"]
                cur = self._barrier_gen.get(name, 0)
                return {"ok": True, "released": cur > gen, "gen": cur}
            if op == "consistent":     # Consistent consensus (:389)
                name, rank, value, count = (req["name"], req["rank"],
                                            req["value"], req["count"])
                st = self._votes.setdefault(
                    name, {"votes": {}, "result": None, "collected": set(),
                           "done_at": None, "started_at": time.time()})
                if st["result"] is not None:
                    # a completed round: hand out the result.  The round
                    # is NOT deleted eagerly on full collection — if the
                    # last collector's response is lost in transit, its
                    # client-side retry must still read the result here
                    # (deleting would recreate a phantom single-vote
                    # round that can never complete).  The monitor's
                    # done_at sweep reclaims it; names are
                    # client-versioned (name#N) so lingering cannot
                    # poison a later round.
                    st["collected"].add(rank)
                    agreed, val = st["result"]
                    return {"ok": True, "done": True, "agreed": agreed,
                            "value": val}
                st["votes"][rank] = value
                if len(st["votes"]) >= count:
                    vals = list(st["votes"].values())
                    agreed = all(v == vals[0] for v in vals)
                    st["result"] = (agreed, vals[0] if agreed else None)
                    st["collected"] = {rank}
                    st["done_at"] = time.time()
                    return {"ok": True, "done": True, "agreed": agreed,
                            "value": vals[0] if agreed else None}
                return {"ok": True, "done": False}
            if op == "membership":     # alive set (elastic re-mesh input)
                return {"ok": True, "alive": sorted(
                    r for r, w in self._workers.items() if w["alive"])}
            if op == "worker_stop":    # WorkerStop broadcast
                ranks = req.get("ranks")
                if ranks is None:
                    ranks = list(self._workers)
                for r in ranks:
                    self._stop_flags.add(r)
                return {"ok": True}
            if op == "resume":        # worker acknowledges the stop and
                                       # rejoins under the new plan
                rank = req["rank"]
                w = self._workers.get(rank)
                if w is None or not w.get("alive"):
                    # a dead-marked worker must reconnect for a fresh rank —
                    # letting it resume would re-enter the old mesh
                    return {"ok": True, "accepted": False}
                self._stop_flags.discard(rank)
                return {"ok": True, "accepted": True}
            if op == "exit":
                rank = req["rank"]
                if rank in self._workers:
                    self._workers[rank]["alive"] = False
                if conn_state is not None:
                    conn_state["clean"] = True
                return {"ok": True}
        raise ValueError(f"unknown op {op!r}")

    def _handle_telemetry(self, op: str, req: Dict[str, Any]) -> Dict[str, Any]:
        """Cluster telemetry plane (docs/observability.md):

        telemetry_push      fold one worker's delta-encoded payload
                            (wire: zlib+base64 JSON — wire.decode_telemetry)
                            into the aggregator.  Idempotent per
                            (worker, boot, seq): retried/duplicated
                            deliveries ack without re-applying, which is
                            what makes the op safe to transport-retry.
        telemetry_snapshot  the live ClusterSnapshot (heartbeat-gap
                            enriched) + the straggler report.  Pure read;
                            observers (tools_cluster.py) may call it on a
                            raw connection without ever joining
                            membership.
        """
        if op == "telemetry_push":
            ack = self.telemetry.ingest(decode_telemetry(req["data"]))
            return {"ok": True, **ack}
        snap = self.cluster_snapshot(window_s=req.get("window_s"))
        return {"ok": True, "snapshot": snap,
                "straggler": self.telemetry.straggler_report(snap)}

    def cluster_snapshot(self, window_s: Optional[float] = None):
        """The live ClusterSnapshot, enriched with per-worker heartbeat
        gaps from the coordination bookkeeping."""
        now = time.time()
        with self._lock:
            hb = {r: now - w["last_beat"] for r, w in self._workers.items()
                  if w.get("alive")}
        return self.telemetry.snapshot(window_s=window_s, heartbeats=hb,
                                       now=now)

    @staticmethod
    def _ps_ids(table, ids) -> np.ndarray:
        """Validated row ids: numpy's negative-index wrapping would silently
        hit the WRONG rows, so reject out-of-range ids of either sign."""
        ids = np.asarray(ids, np.int64)
        if len(ids) and (ids.min() < 0 or ids.max() >= table.shape[0]):
            raise ValueError(
                f"row ids out of range [0, {table.shape[0]}): "
                f"min={ids.min()} max={ids.max()}")
        return ids

    def _handle_ps(self, op: str, req: Dict[str, Any]) -> Dict[str, Any]:
        """Parameter-server embedding tables (reference: v1 PS — hetu/v1
        ps-lite server PSFhandle_embedding.cc pull/push handlers and
        server-side sparse SGD; the HET-paper backing store behind client
        LRU caches, data/embedding_cache.py).  Runs under _ps_lock, NOT the
        coordination lock — see __init__."""
        with self._ps_lock:
            if op == "ps_init":        # idempotent table create
                name = req["name"]
                created = name not in self._ps
                if created:
                    rows, dim = int(req["rows"]), int(req["dim"])
                    kind = req.get("init", "zeros")
                    if kind == "zeros":
                        tab = np.zeros((rows, dim), np.float32)
                    elif kind == "normal":
                        rng = np.random.default_rng(int(req.get("seed", 0)))
                        tab = (rng.standard_normal((rows, dim)) *
                               float(req.get("scale", 0.02))).astype(
                                   np.float32)
                    else:
                        raise ValueError(f"unknown init {kind!r}")
                    self._ps[name] = tab
                t = self._ps[name]
                return {"ok": True, "created": created,
                        "rows": t.shape[0], "dim": t.shape[1]}
            if op == "ps_pull":        # ids -> base64 float32 rows
                t = self._ps[req["name"]]
                ids = self._ps_ids(t, req["ids"])
                data = np.ascontiguousarray(t[ids]) if len(ids) else \
                    np.zeros((0, t.shape[1]), np.float32)
            elif op == "ps_push":      # assign / add / server-side sgd
                t = self._ps[req["name"]]
                ids = self._ps_ids(t, req["ids"])
                rows = decode_rows(req["data"], len(ids), t.shape[1])
                mode = req.get("mode", "assign")
                if mode == "assign":
                    t[ids] = rows          # last write wins per duplicate
                elif mode == "add":        # duplicates accumulate
                    np.add.at(t, ids, rows)
                elif mode == "sgd":        # row -= lr * grad, duplicates sum
                    np.add.at(t, ids, -float(req.get("lr", 0.01)) * rows)
                else:
                    raise ValueError(f"unknown push mode {mode!r}")
                return {"ok": True}
            else:
                raise ValueError(f"unknown op {op!r}")
        # encode OUTSIDE the ps lock too: only the gather needs the table
        return {"ok": True, "dim": int(data.shape[1]),
                "data": encode_rows(data)}

    def close(self):
        self._shutdown = True
        try:
            self._sock.close()
        except OSError:
            pass
        # also tear down the serving connections: a closed server must not
        # keep absorbing (and acking!) writes on old sockets — clients
        # should see the break and fail over / reconnect
        for conn in list(self._conns):
            try:
                conn.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            try:
                conn.close()
            except OSError:
                pass
        self._conns = []
