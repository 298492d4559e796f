"""Fleet router (ISSUE 8): multi-replica tensor_serve with health-checked
failover, zero-loss re-dispatch, and replica drain.

Covers the consistent-hash ring invariants, the replica spec parser, the
tensor_serve_router element end-to-end over real sockets (round trip,
session affinity, least-loaded spread, SHED when the fleet is empty),
mid-stream failover with exact RESULT-xor-SHED accounting, administrative
drain steering, broker-fed membership (dead advertisements pruned before
the next QUERY answer; the query client's empty-answer backoff re-query),
and the slow fleet-chaos acceptance run: >=4 replicas, >=8 concurrent
client streams, one replica killed mid-run and one drained — every frame
settles exactly once and no stream aborts.
"""
import socket
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.analysis.flow import check_identities
from nnstreamer_tpu.edge.broker import DiscoveryBroker, discover_meta
from nnstreamer_tpu.filters import register_custom_easy
from nnstreamer_tpu.serve.router import HashRing, parse_replicas

CAPS4 = ('other/tensors,format=static,num_tensors=1,'
         'types=(string)float32,dimensions=(string)4')


def _free_port():
    s = socket.socket()
    s.bind(("localhost", 0))
    port = s.getsockname()[1]
    s.close()
    return port


@pytest.fixture(scope="module", autouse=True)
def _fleet_models():
    register_custom_easy("fleet_double", lambda x: x * 2)
    yield


def _serve_pipeline(ident, port=0, broker_port=0, topic=""):
    hybrid = (f"connect-type=HYBRID topic={topic} dest-port={broker_port} "
              if topic else "")
    return parse_launch(
        f"tensor_serve_src name=src port={port} id={ident} buckets=1,2,4 "
        f"max-wait-ms=2 {hybrid}"
        "! tensor_filter framework=custom-easy model=fleet_double "
        f"! tensor_serve_sink id={ident}")


def _client_pipeline(port, max_request=8):
    return parse_launch(
        f'appsrc name=in caps="{CAPS4}" '
        f"! tensor_query_client name=qc port={port} timeout=15 "
        f"max-request={max_request} ! appsink name=out")


def _push(client, values):
    for v in values:
        client["in"].push_buffer(Buffer.from_arrays(
            [np.full(4, float(v), np.float32)]))


def _settled(client):
    return len(client["out"].buffers) + client["qc"].stats["shed"]


def _wait_settled(client, want, timeout=30):
    deadline = time.monotonic() + timeout
    while _settled(client) < want and time.monotonic() < deadline:
        time.sleep(0.02)
    return sorted(float(b.chunks[0].host()[0])
                  for b in client["out"].buffers)


# ------------------------------------------------------------------ ring

class TestHashRing:
    def test_lookup_is_deterministic_and_covers_members(self):
        r = HashRing()
        r.rebuild(["a:1", "b:2", "c:3"])
        picks = [r.lookup(f"s{i}") for i in range(200)]
        assert picks == [r.lookup(f"s{i}") for i in range(200)]
        assert set(picks) == {"a:1", "b:2", "c:3"}  # no starved member

    def test_member_loss_only_moves_its_own_keys(self):
        r = HashRing()
        r.rebuild(["a:1", "b:2", "c:3"])
        before = {f"s{i}": r.lookup(f"s{i}") for i in range(200)}
        r.rebuild(["a:1", "c:3"])  # b leaves
        for key, owner in before.items():
            if owner != "b:2":
                # consistent hashing: survivors keep their sessions
                assert r.lookup(key) == owner
            else:
                assert r.lookup(key) in {"a:1", "c:3"}

    def test_empty_ring_returns_none(self):
        r = HashRing()
        r.rebuild([])
        assert r.lookup("anything") is None

    def test_stable_across_instances(self):
        # sha1-based, not the salted builtin hash: two routers (or a
        # restarted one) agree on placement
        a, b = HashRing(), HashRing()
        a.rebuild(["x:1", "y:2"])
        b.rebuild(["x:1", "y:2"])
        assert [a.lookup(f"k{i}") for i in range(50)] == \
            [b.lookup(f"k{i}") for i in range(50)]


class TestParseReplicas:
    def test_formats(self):
        assert parse_replicas("h1:1, h2:2;h3:3") == \
            [("h1", 1), ("h2", 2), ("h3", 3)]
        assert parse_replicas("") == []
        assert parse_replicas("  ") == []

    def test_rejects_garbage(self):
        with pytest.raises(ValueError):
            parse_replicas("no-port")


# ------------------------------------------------------------ end-to-end

class TestRouterE2E:
    def test_round_trip_and_health(self):
        reps = [_serve_pipeline(60 + i) for i in range(2)]
        for sp in reps:
            sp.start()
        ports = [sp["src"].bound_port for sp in reps]
        rp = parse_launch(
            f"tensor_serve_router name=rt port=0 "
            f"replicas=localhost:{ports[0]},localhost:{ports[1]} "
            "heartbeat-ms=50")
        rp.start()
        rt = rp["rt"]
        c = _client_pipeline(rt.bound_port)
        c.start()
        try:
            _push(c, range(8))
            got = _wait_settled(c, 8)
            assert got == [2.0 * i for i in range(8)]
            st = rt.stats.snapshot()
            assert st["router_requests"] == 8
            assert st["router_delivered"] == 8
            assert st["router_shed"] == 0
            assert st["router_orphaned"] == 0
            # heartbeats flowed: both replicas healthy with load reports
            # (a beat that a loaded machine delivers late reads "suspect"
            # until the next one: wait for it, bounded)
            deadline = time.monotonic() + 5.0
            while True:
                time.sleep(0.2)
                rep = rt.router_report()
                if time.monotonic() > deadline or all(
                        r["state"] == "healthy" for r in rep.values()):
                    break
            assert set(rep) == {f"localhost:{p}" for p in ports}
            for r in rep.values():
                assert r["state"] == "healthy"
                assert r["breaker"] == "closed"
                assert r["pongs"] >= 1
                assert "depth" in r["load"]
            # replica links keep a bounded per-op timeout: a wedged
            # replica whose TCP buffer fills must raise into
            # _replica_down, never block the fleet-wide maintenance
            # thread's PING under the send lock forever
            for rob in rt.router._replicas.values():
                assert rob.sock.gettimeout() == rt.router.timeout
        finally:
            c["in"].end_stream()
            c.stop()
            rp.stop()
            for sp in reps:
                sp.stop()

    def test_affinity_pins_stream_to_one_replica(self):
        reps = [_serve_pipeline(62 + i) for i in range(2)]
        for sp in reps:
            sp.start()
        ports = [sp["src"].bound_port for sp in reps]
        rp = parse_launch(
            f"tensor_serve_router name=rt port=0 affinity=true "
            f"replicas=localhost:{ports[0]},localhost:{ports[1]}")
        rp.start()
        c = _client_pipeline(rp["rt"].bound_port)
        c.start()
        try:
            _push(c, range(10))
            assert len(_wait_settled(c, 10)) == 10
            completed = [sp["src"].scheduler.report()["completed"]
                         for sp in reps]
            # one stream, one session key: every frame on ONE replica
            assert sorted(completed) == [0, 10]
        finally:
            c["in"].end_stream()
            c.stop()
            rp.stop()
            for sp in reps:
                sp.stop()

    def test_least_loaded_spreads_without_affinity(self):
        reps = [_serve_pipeline(64 + i) for i in range(2)]
        for sp in reps:
            sp.start()
        ports = [sp["src"].bound_port for sp in reps]
        rp = parse_launch(
            f"tensor_serve_router name=rt port=0 affinity=false "
            f"replicas=localhost:{ports[0]},localhost:{ports[1]}")
        rp.start()
        c = _client_pipeline(rp["rt"].bound_port, max_request=16)
        c.start()
        try:
            _push(c, range(16))
            assert len(_wait_settled(c, 16)) == 16
            completed = [sp["src"].scheduler.report()["completed"]
                         for sp in reps]
            assert sum(completed) == 16
            assert min(completed) > 0  # both replicas pulled their weight
        finally:
            c["in"].end_stream()
            c.stop()
            rp.stop()
            for sp in reps:
                sp.stop()

    def test_empty_fleet_sheds_with_retry_after(self):
        # a replica spec pointing at nothing: every frame must settle
        # as SHED (never hang, never abort)
        rp = parse_launch(
            f"tensor_serve_router name=rt port=0 "
            f"replicas=localhost:{_free_port()} retry-after-ms=20")
        rp.start()
        c = _client_pipeline(rp["rt"].bound_port)
        c.start()
        try:
            _push(c, range(4))
            deadline = time.monotonic() + 15
            while c["qc"].stats["shed"] < 4 and time.monotonic() < deadline:
                time.sleep(0.02)
            assert c["qc"].stats["shed"] == 4
            assert c["out"].buffers == []
            st = rp["rt"].stats.snapshot()
            assert st["router_shed"] == 4
            assert st["router_requests"] == 4
        finally:
            c["in"].end_stream()
            c.stop()
            rp.stop()

    def test_failover_mid_stream_zero_loss(self):
        reps = [_serve_pipeline(66 + i) for i in range(2)]
        for sp in reps:
            sp.start()
        ports = [sp["src"].bound_port for sp in reps]
        rp = parse_launch(
            f"tensor_serve_router name=rt port=0 "
            f"replicas=localhost:{ports[0]},localhost:{ports[1]} "
            "heartbeat-ms=50 breaker-reset-ms=200")
        rp.start()
        rt = rp["rt"]
        c = _client_pipeline(rt.bound_port)
        c.start()
        try:
            _push(c, range(4))
            assert len(_wait_settled(c, 4)) == 4
            # find the replica serving this stream and kill exactly it
            loads = [sp["src"].scheduler.report()["completed"]
                     for sp in reps]
            victim = loads.index(max(loads))
            reps[victim].stop()
            time.sleep(0.3)
            _push(c, range(4, 12))
            got = _wait_settled(c, 12)
            n_shed = c["qc"].stats["shed"]
            # exact accounting: every frame RESULT xor SHED, none lost
            assert len(got) + n_shed == 12
            assert c["qc"].stats["session_declared_lost"] == 0
            assert set(got) <= {2.0 * i for i in range(12)}
            st = rt.stats.snapshot()
            assert st["router_replica_deaths"] >= 1
            # the declared conservation identity replaces hand-written
            # counter math: every accepted request was delivered, shed,
            # or declared orphaned — nothing silently vanished in the
            # failover
            check_identities(st, names=["router-settlement"])
            assert st["router_orphaned"] == 0
            rep = rt.router_report()
            assert rep[f"localhost:{ports[victim]}"]["state"] in \
                ("down", "connecting")
        finally:
            c["in"].end_stream()
            c.stop()
            rp.stop()
            for sp in reps:
                sp.stop()

    def test_drain_replica_steers_sessions_elsewhere(self):
        reps = [_serve_pipeline(68 + i) for i in range(2)]
        for sp in reps:
            sp.start()
        ports = [sp["src"].bound_port for sp in reps]
        rp = parse_launch(
            f"tensor_serve_router name=rt port=0 "
            f"replicas=localhost:{ports[0]},localhost:{ports[1]}")
        rp.start()
        rt = rp["rt"]
        c = _client_pipeline(rt.bound_port)
        c.start()
        try:
            _push(c, range(6))
            assert len(_wait_settled(c, 6)) == 6
            loads = [sp["src"].scheduler.report()["completed"]
                     for sp in reps]
            pinned = loads.index(max(loads))
            assert rt.drain_replica(f"localhost:{ports[pinned]}")
            assert rt.router_report()[
                f"localhost:{ports[pinned]}"]["state"] == "draining"
            # the drained member keeps its link (in-flight still settles)
            # but the affinity session steers to the survivor
            _push(c, range(6, 12))
            got = _wait_settled(c, 12)
            assert len(got) + c["qc"].stats["shed"] == 12
            after = [sp["src"].scheduler.report()["completed"]
                     for sp in reps]
            assert after[pinned] == loads[pinned]  # drained: no new work
            assert after[1 - pinned] > loads[1 - pinned]
            assert rt.stats.snapshot()["router_replica_drains"] == 1
        finally:
            c["in"].end_stream()
            c.stop()
            rp.stop()
            for sp in reps:
                sp.stop()

    def test_trace_report_surfaces_router_block(self):
        reps = [_serve_pipeline(70)]
        reps[0].start()
        port = reps[0]["src"].bound_port
        rp = parse_launch(
            f"tensor_serve_router name=rt port=0 replicas=localhost:{port}")
        tracer = rp.enable_tracing()
        rp.start()
        c = _client_pipeline(rp["rt"].bound_port)
        c.start()
        try:
            _push(c, range(3))
            assert len(_wait_settled(c, 3)) == 3
            rep = tracer.report(rp)
            assert f"localhost:{port}" in rep["rt"]["router"]
            assert rep["rt"]["router"][f"localhost:{port}"]["state"] == \
                "healthy"
        finally:
            c["in"].end_stream()
            c.stop()
            rp.stop()
            reps[0].stop()


# -------------------------------------------------- broker-fed membership

class TestBrokerFleet:
    def test_register_query_counters(self):
        broker = DiscoveryBroker(port=0)
        broker.start()
        try:
            sp = _serve_pipeline(72, broker_port=broker.bound_port,
                                 topic="flt-a")
            sp.start()
            time.sleep(0.1)
            eps = discover_meta("localhost", broker.bound_port, "flt-a")
            assert len(eps) == 1
            (_, port), meta = eps[0]
            assert port == sp["src"].bound_port
            assert meta.get("role") == "serve"  # REGISTER occupancy meta
            assert "depth" in meta
            st = broker.stats.snapshot()
            assert st["broker_registers"] == 1
            assert st["broker_queries"] == 1
            assert st["broker_errors"] == 0
            sp.stop()
        finally:
            broker.stop()

    def test_broker_stats_surface_in_trace_report(self):
        from nnstreamer_tpu.obs.report import Tracer
        broker = DiscoveryBroker(port=0)
        broker.start()
        try:
            discover_meta("localhost", broker.bound_port, "none")
            rep = Tracer().report()
            assert rep["broker"]["broker_queries"] >= 1
        finally:
            broker.stop()

    def test_dead_register_pruned_before_next_query(self):
        """Satellite 3: two servers register; one's REGISTER connection
        dies; the very next QUERY answer must only list the survivor —
        no window where a client can be handed a corpse."""
        broker = DiscoveryBroker(port=0)
        broker.start()
        try:
            reps = [_serve_pipeline(74 + i, broker_port=broker.bound_port,
                                    topic="flt-b") for i in range(2)]
            for sp in reps:
                sp.start()
            time.sleep(0.1)
            eps = discover_meta("localhost", broker.bound_port, "flt-b")
            assert len(eps) == 2
            # sever server 0's REGISTER link (last-will): the broker must
            # drop the advertisement before answering the next QUERY
            reps[0]["src"]._broker_sock.close()
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                eps = discover_meta("localhost", broker.bound_port, "flt-b")
                if len(eps) == 1:
                    break
                time.sleep(0.02)
            assert [e for e, _ in eps] == \
                [("localhost", reps[1]["src"].bound_port)]
            for sp in reps:
                sp.stop()
        finally:
            broker.stop()

    def test_router_follows_broker_and_fails_over(self):
        """Satellite 3, router half: a broker-fed router keeps a client
        stream alive across a replica death — the membership change and
        the link death both steer traffic to the survivor, with zero
        frames lost and no stream abort."""
        broker = DiscoveryBroker(port=0)
        broker.start()
        reps = [_serve_pipeline(76 + i, broker_port=broker.bound_port,
                                topic="flt-c") for i in range(2)]
        for sp in reps:
            sp.start()
        time.sleep(0.1)
        rp = parse_launch(
            f"tensor_serve_router name=rt port=0 topic=flt-c "
            f"dest-port={broker.bound_port} requery-ms=100 heartbeat-ms=50")
        rp.start()
        rt = rp["rt"]
        time.sleep(0.3)
        assert len(rt.router.replica_keys()) == 2
        c = _client_pipeline(rt.bound_port)
        c.start()
        try:
            _push(c, range(4))
            assert len(_wait_settled(c, 4)) == 4
            loads = [sp["src"].scheduler.report()["completed"]
                     for sp in reps]
            victim = loads.index(max(loads))
            reps[victim].stop()
            time.sleep(0.5)
            _push(c, range(4, 10))
            got = _wait_settled(c, 10)
            assert len(got) + c["qc"].stats["shed"] == 10
            assert c["qc"].stats["session_declared_lost"] == 0
            assert c["qc"].stats["reconnects"] == 0  # stream never broke
            # membership followed the broker: the corpse is gone
            deadline = time.monotonic() + 5
            while time.monotonic() < deadline:
                if len(rt.router.replica_keys()) == 1:
                    break
                time.sleep(0.05)
            assert len(rt.router.replica_keys()) == 1
        finally:
            c["in"].end_stream()
            c.stop()
            rp.stop()
            for sp in reps:
                sp.stop()
            broker.stop()

    def test_client_empty_broker_answer_backs_off_then_connects(self):
        """Satellite 2: a query client whose broker query returns ZERO
        endpoints must enter the fault layer's backoff re-query loop
        (accounted as link_errors), not fail the stream fast — and
        connect as soon as a server registers."""
        broker = DiscoveryBroker(port=0)
        broker.start()
        c = parse_launch(
            f'appsrc name=in caps="{CAPS4}" '
            f"! tensor_query_client name=qc connect-type=HYBRID "
            f"topic=flt-d dest-port={broker.bound_port} timeout=15 "
            "max-request=8 ! appsink name=out")
        c.start()
        sp = None
        try:
            time.sleep(0.4)  # several empty answers -> backoff loop
            assert c["qc"].stats["link_errors"] >= 1
            assert c.running  # the stream did NOT fail fast
            sp = _serve_pipeline(78, broker_port=broker.bound_port,
                                 topic="flt-d")
            sp.start()
            _push(c, range(4))
            got = _wait_settled(c, 4)
            assert len(got) + c["qc"].stats["shed"] == 4
        finally:
            c["in"].end_stream()
            c.stop()
            if sp is not None:
                sp.stop()
            broker.stop()

    def test_query_ack_snapshot_stays_aligned_under_churn(self):
        """The QUERY_ACK's endpoints / endpoints_meta lists come from ONE
        consistent snapshot: a REGISTER or disconnect cleanup landing
        mid-answer must never zip one replica's occupancy metadata onto
        a different endpoint."""
        from nnstreamer_tpu.edge.protocol import MsgKind, send_msg
        broker = DiscoveryBroker(port=0)
        broker.start()
        regs = []
        try:
            for i in range(2):  # two stable, distinguishable registrations
                s = socket.create_connection(("localhost",
                                              broker.bound_port))
                send_msg(s, MsgKind.REGISTER,
                         {"topic": "flt-e", "host": f"h{i}",
                          "port": 1000 + i, "meta": {"ident": i}})
                regs.append(s)
            time.sleep(0.1)
            stop = threading.Event()

            def churn():  # a third member flapping register/death
                while not stop.is_set():
                    s = socket.create_connection(("localhost",
                                                  broker.bound_port))
                    send_msg(s, MsgKind.REGISTER,
                             {"topic": "flt-e", "host": "hx", "port": 9999,
                              "meta": {"ident": "x"}})
                    s.close()
            t = threading.Thread(target=churn, daemon=True)
            t.start()
            try:
                valid = {("h0", 1000): 0, ("h1", 1001): 1, ("hx", 9999): "x"}
                for _ in range(50):
                    for ep, info in discover_meta(
                            "localhost", broker.bound_port, "flt-e"):
                        # every endpoint rides with ITS OWN metadata
                        assert info.get("ident") == valid[ep]
            finally:
                stop.set()
                t.join(timeout=5)
        finally:
            for s in regs:
                s.close()
            broker.stop()


# ------------------------------------------------- failover race regressions

class TestFailoverRaces:
    """Unit-level pins for the dispatch/failover/settle races: a never-
    started FleetRouter (no listener, no threads) driven directly."""

    def _bare_router(self):
        from nnstreamer_tpu.serve.router import FleetRouter
        return FleetRouter(port=0)

    def test_send_failure_pop_miss_cedes_retry_to_sweep(self):
        """Double-dispatch race: the dispatcher's send fails BECAUSE a
        concurrent _replica_down severed the socket — and that path's
        failover sweep already reclaimed and re-dispatched the pending
        entry. The sender's exception path must read the pop miss as
        'someone else owns the retry' and stop, not dispatch the same
        request again under a fresh rseq."""
        r = self._bare_router()
        buf = Buffer.from_arrays([np.zeros(4, np.float32)])

        class _RacedSock:
            def sendmsg(self, *a, **k):
                # the sweep wins the race at the worst moment: the entry
                # is gone (and re-homed) by the time this send raises
                with r._plock:
                    r._pending.clear()
                raise BrokenPipeError("severed by _replica_down")

            def sendall(self, *a, **k):
                self.sendmsg()

        picks = []

        def fake_pick(skey, exclude):
            picks.append(set(exclude))
            # a buggy retry loop would come back for a second pick
            return (("r:1", _RacedSock(), threading.Lock(), None)
                    if len(picks) == 1 else None)

        r._pick = fake_pick
        r._dispatch(0, buf, 1, None)
        st = r.stats.snapshot()
        assert len(picks) == 1  # no second dispatch attempt
        assert st["router_requests"] == 1
        assert st["router_shed"] == 0  # the sweep owns the settle now
        assert r.pending() == 0

    def test_late_answer_for_dead_client_is_orphan_not_dup(self):
        """_settle classifies a miss: an answer owed to a client that
        disconnected first is an orphan answer, not a failover
        duplicate — client churn must not inflate router_dup_drops."""
        r = self._bare_router()
        buf = Buffer.from_arrays([np.zeros(4, np.float32)])
        with r._plock:
            r._rseq += 1
            rseq = r._rseq
            r._pending[rseq] = [7, 1, buf, "r:1", 0]
        r._drop_client(7)
        assert r.stats.snapshot()["router_orphaned"] == 1
        assert r._settle(rseq) is None  # the replica answers late
        st = r.stats.snapshot()
        assert st["router_orphan_drops"] == 1
        assert st["router_dup_drops"] == 0
        # a miss with no orphan record IS a failover duplicate
        assert r._settle(999) is None
        st = r.stats.snapshot()
        assert st["router_dup_drops"] == 1
        assert st["router_orphan_drops"] == 1


# ------------------------------------------------------- chaos acceptance

@pytest.mark.slow
class TestFleetChaos:
    N_REPLICAS = 4
    N_CLIENTS = 8
    N_FRAMES = 12

    def test_kill_and_drain_zero_loss(self):
        """The acceptance scenario: 4 broker-registered replicas behind
        one router, 8 concurrent client streams; mid-run one replica is
        killed and another administratively drained. Every request must
        settle RESULT xor SHED (never dropped, never duplicated), no
        client stream aborts, and the affinity sessions of the killed
        and drained replicas resume on survivors."""
        broker = DiscoveryBroker(port=0)
        broker.start()
        reps = [_serve_pipeline(80 + i, broker_port=broker.bound_port,
                                topic="flt-chaos")
                for i in range(self.N_REPLICAS)]
        for sp in reps:
            sp.start()
        time.sleep(0.2)
        rp = parse_launch(
            f"tensor_serve_router name=rt port=0 topic=flt-chaos "
            f"dest-port={broker.bound_port} requery-ms=100 "
            "heartbeat-ms=50 breaker-reset-ms=300")
        rp.start()
        rt = rp["rt"]
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline and \
                len(rt.router.replica_keys()) < self.N_REPLICAS:
            time.sleep(0.05)
        assert len(rt.router.replica_keys()) == self.N_REPLICAS
        barrier = threading.Barrier(self.N_CLIENTS + 1, timeout=30)
        results = {}

        def run_client(tag):
            c = _client_pipeline(rt.bound_port, max_request=16)
            c.start()
            half = self.N_FRAMES // 2
            _push(c, [100.0 * tag + i for i in range(half)])
            _wait_settled(c, half, timeout=60)
            barrier.wait()   # all streams live -> inject the faults
            barrier.wait()   # faults injected -> second half
            _push(c, [100.0 * tag + i for i in range(half, self.N_FRAMES)])
            got = _wait_settled(c, self.N_FRAMES, timeout=60)
            st = c["qc"].stats.snapshot()
            results[tag] = {
                "got": got, "shed": st["shed"],
                "declared_lost": st["session_declared_lost"],
                "reconnects": st["reconnects"],
                "error": c._error,
            }
            c["in"].end_stream()
            c.stop()

        threads = [threading.Thread(target=run_client, args=(t,))
                   for t in range(self.N_CLIENTS)]
        for t in threads:
            t.start()
        barrier.wait()  # every client has its first half settled
        # fault 1: kill the busiest replica outright (process death)
        loads = [sp["src"].scheduler.report()["completed"] for sp in reps]
        victim = loads.index(max(loads))
        victim_key = f"localhost:{reps[victim]['src'].bound_port}"
        reps[victim].stop()
        # fault 2: administratively drain the next-busiest survivor
        loads[victim] = -1
        drained = loads.index(max(loads))
        drained_key = f"localhost:{reps[drained]['src'].bound_port}"
        assert rt.drain_replica(drained_key)
        time.sleep(0.5)
        barrier.wait()  # release the second half
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads)

        assert len(results) == self.N_CLIENTS
        for tag, r in results.items():
            assert r["error"] is None, f"client {tag} aborted: {r}"
            # RESULT xor SHED for every frame; nothing lost, nothing dup
            assert len(r["got"]) + r["shed"] == self.N_FRAMES, \
                f"client {tag}: {r}"
            assert r["declared_lost"] == 0, f"client {tag}: {r}"
            assert r["reconnects"] == 0, f"client {tag}: {r}"
            expected = {2.0 * (100.0 * tag + i)
                        for i in range(self.N_FRAMES)}
            assert set(r["got"]) <= expected  # its OWN frames, once each
            assert len(r["got"]) == len(set(r["got"]))

        st = rt.stats.snapshot()
        sent = st["router_requests"]
        assert sent == self.N_CLIENTS * self.N_FRAMES
        # the router-side ledger balances exactly: the declared
        # conservation identity covers every admitted frame
        check_identities(st, names=["router-settlement"])
        assert st["router_orphaned"] == 0
        assert st["router_replica_deaths"] >= 1

        # affinity resumed on survivors: no session maps to the dead or
        # draining member any more
        live = {k for k, v in rt.router_report().items()
                if v["state"] == "healthy"}
        assert victim_key not in live and drained_key not in live
        assert live  # survivors exist
        for i in range(64):
            owner = rt.router.assignment(f"probe-{i}")
            assert owner in live

        rp.stop()
        for i, sp in enumerate(reps):
            if i != victim:
                sp.stop()
        broker.stop()


# ------------------------------------- rejoin / resurrection regressions

def _wait_for(pred, timeout=15):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


class TestRejoinResurrect:
    """Direct coverage for the replica rejoin/resurrection ledger
    (previously only exercised incidentally) and the mid-drain rejoin
    drift fix: an administrative drain must survive a TCP blip to the
    same process life, and must be cleared by a genuinely new process
    taking over the endpoint."""

    def test_ledger_counters_seeded_at_zero(self):
        sp = _serve_pipeline(80)
        sp.start()
        port = sp["src"].bound_port
        rp = parse_launch(
            f"tensor_serve_router name=rt port=0 replicas=localhost:{port}")
        rp.start()
        try:
            st = rp["rt"].stats.snapshot()
            # present before any event: dashboards/tests can rely on the
            # keys existing, and flow tooling sees them produced
            assert st["router_replica_rejoins"] == 0
            assert st["router_replica_resurrections"] == 0
        finally:
            rp.stop()
            sp.stop()

    def test_new_process_on_same_port_clears_drain_counts_rejoin(self):
        port = _free_port()
        sp = _serve_pipeline(81, port=port)
        sp.start()
        rp = parse_launch(
            f"tensor_serve_router name=rt port=0 replicas=localhost:{port} "
            "heartbeat-ms=50 breaker-reset-ms=100")
        rp.start()
        rt = rp["rt"]
        key = f"localhost:{port}"
        sp2 = None
        try:
            assert _wait_for(
                lambda: rt.router_report()[key]["state"] == "healthy")
            assert rt.drain_replica(key)
            assert rt.router_report()[key]["state"] == "draining"
            # the drained process exits; a NEW process takes the port
            sp.stop()
            deadline = time.monotonic() + 10
            while True:  # the old listener may need a beat to release
                sp2 = _serve_pipeline(81, port=port)
                try:
                    sp2.start()
                    break
                except OSError:
                    if time.monotonic() > deadline:
                        raise
                    time.sleep(0.1)
            # the rejoin is a different process life (fresh instance
            # token): the stale administrative drain must not outlive
            # the process it was aimed at
            assert _wait_for(
                lambda: rt.router_report()[key]["state"] == "healthy")
            st = rt.stats.snapshot()
            assert st["router_replica_rejoins"] == 1
            assert st["router_replica_resurrections"] == 0
        finally:
            rp.stop()
            for p in (sp2,):
                if p is not None:
                    p.stop()

    def test_socket_blip_same_process_keeps_drain(self):
        sp = _serve_pipeline(82)
        sp.start()
        port = sp["src"].bound_port
        rp = parse_launch(
            f"tensor_serve_router name=rt port=0 replicas=localhost:{port} "
            "heartbeat-ms=50 breaker-reset-ms=100")
        rp.start()
        rt = rp["rt"]
        key = f"localhost:{port}"
        try:
            assert _wait_for(
                lambda: rt.router_report()[key]["state"] == "healthy")
            assert rt.drain_replica(key)
            # sever the TCP link only — the replica process lives on
            assert rt.kill_link() >= 1
            core = rt.router
            assert _wait_for(
                lambda: core._replicas[key].sock is not None)
            # same process life (same instance token echoed in the
            # CAPS_ACK): the reconnect is a link blip, NOT a rejoin —
            # the drain stays and the ledger does not drift
            assert rt.router_report()[key]["state"] == "draining"
            assert rt.stats.snapshot()["router_replica_rejoins"] == 0
        finally:
            rp.stop()
            sp.stop()

    def test_resurrection_advert_edge_triggered(self):
        from nnstreamer_tpu.edge.protocol import MsgKind, send_msg
        broker = DiscoveryBroker(port=0)
        broker.start()
        dead_port = _free_port()  # nothing listens: advert only

        def advertise(sessions):
            s = socket.create_connection(
                ("localhost", broker.bound_port), timeout=5)
            send_msg(s, MsgKind.REGISTER,
                     {"topic": "flt-rz", "host": "localhost",
                      "port": dead_port,
                      "meta": {"role": "serve", "depth": 0,
                               "restored_sessions": sessions}})
            return s

        rp = parse_launch(
            "tensor_serve_router name=rt port=0 topic=flt-rz "
            f"dest-port={broker.bound_port} requery-ms=100 "
            "breaker-reset-ms=200")
        rp.start()
        rt = rp["rt"]
        key = f"localhost:{dead_port}"
        resur = lambda: rt.stats.snapshot()["router_replica_resurrections"]
        reg = reg2 = None
        try:
            reg = advertise(["s1", "s2"])
            assert _wait_for(lambda: resur() == 1)
            # edge-triggered, not level: the advert persists across
            # requeries but the resurrection is counted exactly once
            time.sleep(0.5)
            assert resur() == 1
            # the advert dies with its registration connection...
            reg.close()
            assert _wait_for(lambda: key not in rt.router_report())
            # ...and the next restored_sessions advert is a FRESH edge
            reg2 = advertise(["s1"])
            assert _wait_for(lambda: resur() == 2)
        finally:
            for s in (reg, reg2):
                if s is not None:
                    try:
                        s.close()
                    except OSError:
                        pass
            rp.stop()
            broker.stop()
