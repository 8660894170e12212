"""Coordination service tests (reference: the DeviceController surface —
untestable there without a cluster; here it's localhost threads)."""
import threading
import time

import pytest

from hetu_tpu.rpc import CoordinationClient, CoordinationServer


@pytest.fixture
def server():
    s = CoordinationServer(world_size=4, heartbeat_timeout=1.0)
    yield s
    s.close()


def _client(server, **kw):
    return CoordinationClient("127.0.0.1", server.port, auto_heartbeat=False,
                              **kw)


def test_connect_assigns_ranks(server):
    c0, c1, c2 = (_client(server) for _ in range(3))
    assert [c0.rank, c1.rank, c2.rank] == [0, 1, 2]
    assert c0.world_size == 4


def test_kv_store(server):
    c0, c1 = _client(server), _client(server)
    c0.put("strategy", {"tp": 4, "dp": 2})
    assert c1.get("strategy") == {"tp": 4, "dp": 2}
    with pytest.raises(KeyError):
        c1.get("missing")
    # blocking get woken by a later put
    out = {}

    def waiter():
        out["v"] = c1.get("late", block=True, timeout=5)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.1)
    c0.put("late", 42)
    t.join(timeout=5)
    assert out["v"] == 42


def test_barrier(server):
    clients = [_client(server) for _ in range(3)]
    order = []

    def enter(c, i):
        c.barrier("sync", count=3)
        order.append(i)

    threads = [threading.Thread(target=enter, args=(c, i))
               for i, c in enumerate(clients)]
    for t in threads[:2]:
        t.start()
    time.sleep(0.2)
    assert order == []          # nobody released yet
    threads[2].start()
    for t in threads:
        t.join(timeout=10)
    assert sorted(order) == [0, 1, 2]


def test_consistent_vote(server):
    c0, c1 = _client(server), _client(server)
    res = {}

    def vote(c, v, key):
        res[key] = c.consistent("plan", v, count=2)

    t0 = threading.Thread(target=vote, args=(c0, "tp4", "a"))
    t1 = threading.Thread(target=vote, args=(c1, "tp4", "b"))
    t0.start(); t1.start()
    t0.join(5); t1.join(5)
    assert res == {"a": "tp4", "b": "tp4"}


def test_heartbeat_failure_detection(server):
    c0 = CoordinationClient("127.0.0.1", server.port,
                            heartbeat_interval=0.2)  # auto heartbeat
    c1 = _client(server)  # never beats after connect
    time.sleep(2.0)       # > heartbeat_timeout (1s)
    alive = c0.membership()
    assert 0 in alive and 1 not in alive
    c0.exit()


def test_worker_stop_broadcast(server):
    c0 = CoordinationClient("127.0.0.1", server.port, heartbeat_interval=0.1)
    c1 = _client(server)
    c1.worker_stop([0])
    time.sleep(0.5)
    assert c0.should_stop
    c0.exit()


def test_worker_stop_all(server):
    c0 = CoordinationClient("127.0.0.1", server.port, heartbeat_interval=0.1)
    c1 = CoordinationClient("127.0.0.1", server.port, heartbeat_interval=0.1)
    c1.worker_stop()  # regression: broadcast (ranks=None) must stop everyone
    time.sleep(0.5)
    assert c0.should_stop and c1.should_stop
    c0.exit(); c1.exit()


def test_consistent_vote_name_reuse(server):
    # regression: a second round under the same name must not see stale votes
    c0, c1 = _client(server), _client(server)
    res = {}

    def vote(c, v, key):
        res[key] = c.consistent("plan", v, count=2)

    for rnd, val in enumerate(["tp4", "tp8"]):
        ts = [threading.Thread(target=vote, args=(c, val, f"{rnd}:{i}"))
              for i, c in enumerate([c0, c1])]
        [t.start() for t in ts]
        [t.join(5) for t in ts]
    assert res == {"0:0": "tp4", "0:1": "tp4", "1:0": "tp8", "1:1": "tp8"}


def test_dead_worker_stops_survivors(server):
    # regression: losing a worker must signal stop to the survivors
    c0 = CoordinationClient("127.0.0.1", server.port, heartbeat_interval=0.2)
    c1 = _client(server)  # never heartbeats -> declared dead
    time.sleep(2.5)
    assert c0.should_stop  # survivor told to stop for re-mesh
    c0.exit()


def test_resume_clears_stop_flag(server):
    c = CoordinationClient("127.0.0.1", server.port, heartbeat_interval=0.1)
    c.worker_stop([c.rank])
    time.sleep(0.4)
    assert c.should_stop
    c.resume()
    time.sleep(0.4)
    assert not c.should_stop   # heartbeats no longer re-set it
    c.exit()


def test_resume_rejected_for_dead_rank(server):
    c = CoordinationClient("127.0.0.1", server.port, auto_heartbeat=False)
    # let the monitor declare it dead (no heartbeats), stop flag set
    time.sleep(2.0)
    assert c.rank not in server._handle({"op": "membership"})["alive"]
    with pytest.raises(RuntimeError):
        c.resume()


def test_client_reconnects_after_server_restart():
    """Satellite: reconnect-with-backoff against a server restarted
    mid-run.  The client's next op fails in transit, the reconnect loop
    backs off until the new server (same port, empty state) accepts the
    reattach, and the retried idempotent op completes — rank preserved."""
    import threading

    from hetu_tpu.obs.metrics import get_registry
    reg = get_registry()
    before = reg.counter_value("rpc.reconnects")
    s1 = CoordinationServer(world_size=1, heartbeat_timeout=30.0)
    port = s1.port
    c = CoordinationClient("127.0.0.1", port, auto_heartbeat=False,
                           op_timeout=10.0, max_reconnect_wait=30.0)
    rank = c.rank
    c.put("before", 1)
    # the accept loop lists a connection after it starts serving it;
    # under load `close` can run in between and leave this one open.
    # Made certain here: a put the stopped server then acknowledged was
    # lost, and the get below raised KeyError on the new one
    s1._conns = []
    s1.close()
    holder = {}

    def restart():
        time.sleep(0.8)   # client must survive several refused attempts
        holder["s2"] = CoordinationServer(port=port, world_size=1,
                                          heartbeat_timeout=30.0)

    t = threading.Thread(target=restart, daemon=True)
    t.start()
    try:
        c.put("after", 2)          # retried once the restart lands
        assert c.get("after") == 2
        assert c.rank == rank      # reattach re-claimed the old rank
        assert c.reconnects >= 1
        assert reg.counter_value("rpc.reconnects") - before >= 1
        # the restarted server knows the reattached rank as alive
        t.join(10)
        assert rank in holder["s2"].alive_ranks()
        # and a FRESH connect gets a rank past the re-claimed one
        c2 = CoordinationClient("127.0.0.1", port, auto_heartbeat=False)
        assert c2.rank > rank
        c2.exit()
        c.exit()
    finally:
        if "s2" in holder:
            holder["s2"].close()


def test_socket_break_preserves_rank_within_grace(server):
    """A torn socket + quick reconnect must NOT be treated as worker
    death (the reattach grace window): membership is unchanged and no
    worker-loss event fires."""
    import socket as socket_mod

    from hetu_tpu.obs.metrics import get_registry
    reg = get_registry()
    lost_before = reg.counter_value("rpc.workers_lost",
                                    reason="connection lost")
    c = _client(server)
    c._conn.shutdown(socket_mod.SHUT_RDWR)   # tear the transport
    assert c.membership() == [c.rank]        # reconnect + retried read
    assert c.reconnects == 1
    time.sleep(0.3)
    assert c.rank in c.membership()
    assert reg.counter_value("rpc.workers_lost",
                             reason="connection lost") == lost_before
    c.exit()


def test_heartbeat_loss_is_flagged_not_swallowed():
    """Satellite regression: a dead server must not silently kill the
    heartbeat thread — the client flags it, counts rpc.heartbeat_lost,
    and keeps retrying at beat cadence."""
    from hetu_tpu.obs.metrics import get_registry
    reg = get_registry()
    before = reg.counter_value("rpc.heartbeat_lost")
    s = CoordinationServer(world_size=1, heartbeat_timeout=30.0)
    c = CoordinationClient("127.0.0.1", s.port, heartbeat_interval=0.1,
                           op_timeout=2.0, max_reconnect_wait=0.2)
    assert not c.heartbeat_lost
    s.close()
    deadline = time.time() + 15.0
    while not c.heartbeat_lost:
        assert time.time() < deadline, "heartbeat loss never flagged"
        time.sleep(0.05)
    assert c.disconnected
    assert reg.counter_value("rpc.heartbeat_lost") - before >= 1
    assert c._hb.is_alive()   # still retrying, not silently dead
    c.exit()


def test_accept_loop_prunes_dead_threads(server):
    """Satellite regression: connection threads must not accumulate
    forever across reconnect cycles (unbounded growth on long elastic
    runs)."""
    for _ in range(8):
        c = _client(server)
        c.exit()
    # one live client forces an accept, which prunes the dead threads
    live = _client(server)
    time.sleep(0.2)
    live2 = _client(server)
    assert len(server._threads) <= 4, len(server._threads)
    live.exit()
    live2.exit()


def test_reattach_rejected_for_dead_rank(server):
    """A rank the server declared dead cannot sneak back via reattach
    (split-brain guard): the client surfaces StaleRankError."""
    import socket as socket_mod

    from hetu_tpu.rpc.client import StaleRankError
    c = _client(server)
    server._mark_lost(c.rank, why="test")
    c._conn.shutdown(socket_mod.SHUT_RDWR)
    with pytest.raises(StaleRankError):
        c.membership()
    assert c.stale


def test_vote_result_survives_lost_last_collection(server):
    """Review regression: the completed vote round must outlive full
    collection — if the LAST collector's response is lost in transit, its
    retry re-submits the same round and must read the result, not open a
    phantom single-vote round."""
    h = server._handle
    assert h({"op": "consistent", "name": "p#0", "rank": 0, "value": "a",
              "count": 2})["done"] is False
    done = h({"op": "consistent", "name": "p#0", "rank": 1, "value": "a",
              "count": 2})
    assert done["done"] and done["agreed"]
    # rank 0 collects; rank 1's collection response is "lost" and retried
    assert h({"op": "consistent", "name": "p#0", "rank": 0, "value": "a",
              "count": 2})["done"]
    retry = h({"op": "consistent", "name": "p#0", "rank": 1, "value": "a",
               "count": 2})
    assert retry["done"] and retry["agreed"] and retry["value"] == "a"


def test_distributed_init_single_process(server):
    # single process: jax.distributed untouched; control client connects
    from hetu_tpu.core.distributed import distributed_init
    n, client = distributed_init(
        control_address=f"127.0.0.1:{server.port}")
    assert n >= 1 and client is not None
    client.put("hello", 1)
    assert client.get("hello") == 1
    client.exit()


def test_distributed_init_no_args():
    from hetu_tpu.core.distributed import distributed_init
    n, client = distributed_init()
    assert n >= 1 and client is None
