"""Elastic fleet (ISSUE 18): autoscaler control plane, preemptible-by-
default replicas, zero-downtime blue/green rollouts, and the persistent
compile cache.

Fast tier: the replica-lifecycle conservation identity
(``replicas_spawned == serving + draining + retired + resurrecting``)
driven deterministically through ``Autoscaler.step()`` with faked
replica processes — spawn, scale-up, scale-down (drain→preempt),
unexpected death → resurrect, spawn failure, floor repair, blue/green
replacement — plus the CompileCache registry round trip and the inert
``tensor_autoscaler`` element.

Slow tier (``-m slow``; ``make chaos-elastic``): real subprocess
replicas over a real broker/router — random SIGTERM chaos under client
load with zero-loss settlement proven by ``check_identities`` on BOTH
ledgers (router settlement and fleet lifecycle), a mid-traffic
blue/green version swap with ``declared_lost == 0``, and the warm-start
arm: a compile-cache-warmed replica's first frame lands within 2x its
steady state while the cold control arm shows the compile gap.
"""
import os
import random
import signal
import socket
import threading
import time

import numpy as np
import pytest

from nnstreamer_tpu import Buffer, parse_launch
from nnstreamer_tpu.analysis.flow import check_identities
from nnstreamer_tpu.checkpoint import SnapshotStore
from nnstreamer_tpu.edge.broker import DiscoveryBroker
from nnstreamer_tpu.filters import register_custom_easy
from nnstreamer_tpu.fleet import (Autoscaler, AutoscalerConfig,
                                  BlueGreenRollout, CompileCache,
                                  ReplicaProcess, ReplicaSpec)
from nnstreamer_tpu.fleet import autoscaler as autoscaler_mod
from nnstreamer_tpu.fleet import cache as cache_mod
from nnstreamer_tpu.fleet.autoscaler import DRAINING, RESURRECTING, SERVING

CAPS4 = ('other/tensors,format=static,num_tensors=1,'
         'types=(string)float32,dimensions=(string)4')
CAPS64 = ('other/tensors,format=static,num_tensors=1,'
          'types=(string)float32,dimensions=(string)64')

# registered inside each replica child before parse_launch
PRELUDE = ("from nnstreamer_tpu.filters import register_custom_easy\n"
           "register_custom_easy('fleet_double', lambda x: x * 2)\n")


@pytest.fixture(scope="module", autouse=True)
def _models():
    register_custom_easy("fleet_double", lambda x: x * 2)
    yield


def _wait_for(pred, timeout=30):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(0.02)
    return False


# ------------------------------------------------- compile cache registry

class TestCompileCache:
    SIG = (((1, 64), "float32"),)

    def test_record_dedup_and_reload(self, tmp_path):
        cc = CompileCache(str(tmp_path))
        assert cc.record("jax", "zoo://mlp|mesh=", self.SIG) is True
        assert cc.record("jax", "zoo://mlp|mesh=", self.SIG) is False
        # donation changes the compiled program: a distinct entry
        assert cc.record("jax", "zoo://mlp|mesh=", self.SIG,
                         donate=(1,)) is True
        # a fresh process (new instance) replays the same registry
        cc2 = CompileCache(str(tmp_path))
        assert cc2.signatures("jax", "zoo://mlp|mesh=") == \
            [(self.SIG, ()), (self.SIG, (1,))]
        assert cc2.signatures("fusion", "zoo://mlp|mesh=") == []

    def test_corrupt_registry_starts_cold(self, tmp_path):
        cc = CompileCache(str(tmp_path))
        cc.record("jax", "m", self.SIG)
        snap = SnapshotStore(str(tmp_path)).latest()
        with open(os.path.join(snap, "signatures.json"), "w") as f:
            f.write("not json {")
        # torn registry costs warmup, never correctness
        cc2 = CompileCache(str(tmp_path))
        assert cc2.signatures("jax", "m") == []

    def test_install_active_env_inheritance(self, tmp_path, monkeypatch):
        cache_mod.deactivate()
        monkeypatch.delenv(cache_mod.ENV_VAR, raising=False)
        try:
            assert cache_mod.active() is None
            cc = cache_mod.install(str(tmp_path))
            # exported so spawned replicas converge on the same registry
            assert os.environ[cache_mod.ENV_VAR] == str(tmp_path)
            assert cache_mod.active() is cc
            # a "child" process: nothing installed, env points the way
            cache_mod.deactivate()
            assert cache_mod.active() is not None
            assert cache_mod.active().root == str(tmp_path)
        finally:
            cache_mod.deactivate()
            # plain pop, NOT monkeypatch.delenv: deleting a var that
            # install() set would record an undo entry, and teardown
            # would RESTORE it — leaking an active cache into every
            # later test via active()'s env auto-install
            os.environ.pop(cache_mod.ENV_VAR, None)


# ---------------------------------------- lifecycle identity (fake procs)

class _FakeProc:
    """Deterministic stand-in for ReplicaProcess: same constructor and
    surface, no subprocess."""

    instances = []
    fail_next_spawn = False
    _next_port = 9000

    def __init__(self, spec, ident, port=0, version=None, restore=False):
        self.spec = spec
        self.ident = ident
        if not port:
            _FakeProc._next_port += 1
            port = _FakeProc._next_port
        self.port = int(port)
        self.version = spec.version if version is None else str(version)
        self.restore = bool(restore)
        self.dead = False
        self.was_preempted = False
        self.preempt_report = None
        _FakeProc.instances.append(self)

    @property
    def ckpt_dir(self):
        return os.path.join(self.spec.ckpt_root, self.ident)

    def key(self, host="localhost"):
        return f"{host}:{self.port}"

    def spawn(self):
        if _FakeProc.fail_next_spawn:
            _FakeProc.fail_next_spawn = False
            raise RuntimeError("injected spawn failure")
        return self

    def wait_ready(self, timeout=None):
        return self.port

    def alive(self):
        return not self.dead

    def ready(self):
        return not self.dead

    def preempt(self, timeout=30.0):
        self.was_preempted = True
        self.dead = True
        self.preempt_report = {"drained": 0, "abandoned": 0}
        return self.preempt_report

    def kill(self):
        self.dead = True


class _FakeRouter:
    """report()/drain_replica() surface mirroring the autoscaler's
    replica set, with an injectable p95 signal."""

    def __init__(self):
        self.p95_us = 0.0
        self.depth = 0
        self.drained = []
        self.auto = None

    def report(self):
        out = {}
        if self.auto is not None:
            with self.auto._lock:
                reps = list(self.auto._replicas.values())
            for rp in reps:
                out[rp.key()] = {
                    "state": "healthy", "in_flight": 0,
                    "load": {"queue_delay_us_p95": self.p95_us,
                             "depth": self.depth}}
        return out

    def drain_replica(self, key):
        self.drained.append(key)
        return True


@pytest.fixture
def fleet(monkeypatch, tmp_path):
    _FakeProc.instances = []
    _FakeProc.fail_next_spawn = False
    monkeypatch.setattr(autoscaler_mod, "ReplicaProcess", _FakeProc)
    spec = ReplicaSpec(desc_template="unused", ckpt_root=str(tmp_path))

    def mk(router=None, **cfg_kw):
        auto = Autoscaler(spec, router=router,
                          config=AutoscalerConfig(**cfg_kw), name="t")
        if isinstance(router, _FakeRouter):
            router.auto = auto
        return auto

    return mk


class TestLifecycleIdentity:
    def test_spawn_then_retire_balances(self, fleet):
        auto = fleet()
        ident = auto.spawn_replica()
        auto.check()
        assert auto.replicas() == {ident: SERVING}
        # scale-down: drain (no router here) then preempt, reaped sync
        assert auto.retire_replica(ident, sync=True)
        auto.check()
        life = auto.lifecycle()
        assert life["replicas_spawned"] == 1
        assert life["replicas_retired"] == 1
        assert life["replicas_serving"] == 0
        assert life["replicas_draining"] == 0
        assert _FakeProc.instances[0].was_preempted  # SIGTERM, not kill

    def test_spawn_failure_books_retired(self, fleet):
        auto = fleet()
        _FakeProc.fail_next_spawn = True
        with pytest.raises(RuntimeError):
            auto.spawn_replica()
        auto.check()
        life = auto.lifecycle()
        assert life["replicas_spawned"] == 1
        assert life["replicas_retired"] == 1
        assert auto.replicas() == {}

    def test_unexpected_death_resurrects(self, fleet):
        auto = fleet()
        ident = auto.spawn_replica()
        corpse = auto.handle(ident)
        corpse.dead = True
        auto.step()  # reap: the corpse retires, a restore-spawn begins
        auto.check()
        life = auto.lifecycle()
        assert life["resurrections"] == 1
        assert life["replicas_spawned"] == 2
        assert life["replicas_retired"] == 1
        reborn = auto.handle(ident)
        assert reborn is not corpse
        assert reborn.restore is True
        assert reborn.port == corpse.port  # same endpoint
        # may already be serving (the reap step also promotes ready
        # resurrections); drive once more and it must be
        auto.step()
        auto.check()
        assert auto.replicas() == {ident: SERVING}

    def test_death_without_resurrect_stays_down(self, fleet):
        auto = fleet(resurrect=False, min_replicas=0)
        ident = auto.spawn_replica()
        auto.handle(ident).dead = True
        auto.step()
        auto.check()
        assert auto.replicas() == {}
        assert auto.lifecycle()["replicas_retired"] == 1

    def test_scale_up_on_high_p95_until_max(self, fleet):
        rt = _FakeRouter()
        auto = fleet(router=rt, max_replicas=3, target_delay_ms=50.0,
                     scale_up_cooldown_s=0.0)
        auto.spawn_replica()
        rt.p95_us = 200_000.0  # 200ms >> 50ms target
        for _ in range(5):
            auto.step()
            auto.check()
        life = auto.lifecycle()
        assert life["replicas_serving"] == 3  # capped at max
        assert life["scale_ups"] == 2

    def test_scale_down_drains_then_preempts(self, fleet):
        rt = _FakeRouter()
        auto = fleet(router=rt, min_replicas=1, max_replicas=4,
                     scale_down_cooldown_s=0.0, drain_deadline_ms=200.0)
        for _ in range(2):
            auto.spawn_replica()
        rt.p95_us = 0.0  # idle: under low water
        auto.step()
        assert auto.lifecycle()["scale_downs"] == 1
        # the async drain worker preempts; the loop reaps the exit
        assert _wait_for(
            lambda: (auto.step() or True)
            and auto.lifecycle()["replicas_retired"] == 1, timeout=10)
        auto.check()
        assert len(rt.drained) == 1  # router settled BEFORE the SIGTERM
        assert auto.lifecycle()["replicas_serving"] == 1
        # at the floor: no further scale-down
        auto.step()
        assert auto.lifecycle()["scale_downs"] == 1

    def test_hold_scaling_suspends_control_law(self, fleet):
        rt = _FakeRouter()
        auto = fleet(router=rt, min_replicas=1, max_replicas=4,
                     scale_down_cooldown_s=0.0, scale_up_cooldown_s=0.0)
        for _ in range(2):
            auto.spawn_replica()
        with auto.hold_scaling():
            rt.p95_us = 0.0  # would scale down...
            auto.step()
            rt.p95_us = 500_000.0  # ...or up
            auto.step()
            life = auto.lifecycle()
            assert life["scale_downs"] == 0 and life["scale_ups"] == 0
        auto.step()  # released: the control law acts again
        assert auto.lifecycle()["scale_ups"] == 1
        auto.check()

    def test_floor_repair(self, fleet):
        auto = fleet(min_replicas=2)
        auto.spawn_replica()
        auto.step()  # serving < min: repair without a cooldown gate
        auto.check()
        assert auto.lifecycle()["replicas_serving"] == 2

    def test_blue_green_rollout_replaces_ring(self, fleet):
        rt = _FakeRouter()
        auto = fleet(router=rt)
        for _ in range(2):
            auto.spawn_replica(version="blue")
        res = BlueGreenRollout(auto, "green",
                               routable_timeout_s=5.0).run()
        auto.check()
        assert res["replaced"] == 2
        assert len(res["spawned"]) == 2
        states = auto.replicas()
        assert sorted(states.values()) == [SERVING, SERVING]
        for ident in states:
            assert auto.handle(ident).version == "green"
        life = auto.lifecycle()
        assert life["rollouts"] == 1
        assert life["replicas_retired"] == 2
        # every blue replica was drained before its SIGTERM
        assert len(rt.drained) == 2

    def test_stop_retires_everything(self, fleet):
        auto = fleet()
        for _ in range(3):
            auto.spawn_replica()
        auto.stop()
        auto.check()
        life = auto.lifecycle()
        assert life["replicas_serving"] == 0
        assert life["replicas_draining"] == 0
        assert life["replicas_resurrecting"] == 0
        assert life["replicas_retired"] == 3


class TestAutoscalerElement:
    def test_inert_without_desc_template(self):
        # lintable/launchable with no replica recipe: the control plane
        # only engages when desc-template is set
        p = parse_launch("tensor_autoscaler name=a router=rt")
        p.start()
        try:
            assert p["a"].autoscaler is None
            assert p["a"].session_info() == {}
        finally:
            p.stop()

    def test_identity_is_declared(self):
        from nnstreamer_tpu.analysis.flow.registry import identities_by_name
        ident = identities_by_name()["fleet-replica-lifecycle"]
        assert ident.expression == (
            "replicas_spawned == replicas_serving + replicas_draining "
            "+ replicas_retired + replicas_resurrecting")


# ------------------------------------------- slow: real-subprocess fleet

def _serve_desc(broker_port, topic, with_version=False):
    v = "version={version} " if with_version else ""
    return ("tensor_serve_src name=src port={port} id=90 "
            "buckets=1,2,4 max-wait-ms=2 connect-type=HYBRID "
            f"topic={topic} dest-port={broker_port} {v}"
            "! tensor_filter framework=custom-easy model=fleet_double "
            "! tensor_serve_sink id=90")


def _mk_client(port, max_request=8):
    c = parse_launch(
        f'appsrc name=in caps="{CAPS4}" '
        f"! tensor_query_client name=qc port={port} timeout=15 "
        f"max-request={max_request} ! appsink name=out")
    c.start()
    return c


def _push4(client, values):
    for v in values:
        client["in"].push_buffer(Buffer.from_arrays(
            [np.full(4, float(v), np.float32)]))


def _settled(client):
    return len(client["out"].buffers) + client["qc"].stats["shed"]


@pytest.mark.slow
class TestElasticFleetSlow:
    def _router(self, broker, topic):
        rp = parse_launch(
            f"tensor_serve_router name=rt port=0 topic={topic} "
            f"dest-port={broker.bound_port} requery-ms=100 "
            "heartbeat-ms=50 breaker-reset-ms=300")
        rp.start()
        return rp

    def test_chaos_sigterm_zero_loss(self, tmp_path):
        """Random SIGTERMs against serving replicas under client load:
        every killed replica snapshots and resurrects, every frame
        settles exactly once, and BOTH conservation identities hold
        with zero declared loss."""
        rng = random.Random(1809)
        n_clients, n_frames, n_kills = 4, 12, 2
        broker = DiscoveryBroker(port=0)
        broker.start()
        topic = "elastic-chaos"
        rp = self._router(broker, topic)
        rt = rp["rt"]
        spec = ReplicaSpec(
            desc_template=_serve_desc(broker.bound_port, topic),
            ckpt_root=str(tmp_path / "ckpt"), grace_s=1.5,
            prelude=PRELUDE)
        auto = Autoscaler(
            spec, router=rt,
            config=AutoscalerConfig(
                min_replicas=2, max_replicas=3, interval_s=0.1,
                # chaos arm tests failover, not the control law: park
                # the target high so kills are the only fleet events
                target_delay_ms=1e6),
            name="chaos")
        clients = []
        reports = []
        try:
            auto.start()
            assert _wait_for(
                lambda: len(rt.router.replica_keys()) >= 2, timeout=60)
            clients = [_mk_client(rt.bound_port) for _ in range(n_clients)]
            half = n_frames // 2
            for tag, c in enumerate(clients):
                _push4(c, [100 * tag + i for i in range(half)])
            for c in clients:
                assert _wait_for(lambda c=c: _settled(c) >= half,
                                 timeout=60)

            for round_no in range(n_kills):
                serving = [i for i, s in auto.replicas().items()
                           if s == SERVING]
                victim = rng.choice(serving)
                corpse = auto.handle(victim)
                reports.append(corpse)
                os.kill(corpse.pid, signal.SIGTERM)  # external preemption
                # the guard drains+snapshots, the loop reaps+resurrects
                assert _wait_for(
                    lambda n=round_no: auto.lifecycle()["resurrections"]
                    >= n + 1, timeout=60)
                assert _wait_for(
                    lambda: auto.lifecycle()["replicas_serving"] >= 2
                    and auto.lifecycle()["replicas_resurrecting"] == 0,
                    timeout=120)

            for tag, c in enumerate(clients):
                _push4(c, [100 * tag + i for i in range(half, n_frames)])
            for c in clients:
                assert _wait_for(lambda c=c: _settled(c) >= n_frames,
                                 timeout=60)

            for tag, c in enumerate(clients):
                st = c["qc"].stats.snapshot()
                got = sorted(float(b.chunks[0].host()[0])
                             for b in c["out"].buffers)
                # RESULT xor SHED per frame, zero declared lost
                assert len(got) + st["shed"] == n_frames, (tag, st)
                assert st["session_declared_lost"] == 0, (tag, st)
                assert len(got) == len(set(got)), (tag, got)
                assert c._error is None

            # every SIGTERM'd child reported its drain/abandon
            # accounting as its last words, and left a snapshot behind
            for corpse in reports:
                assert _wait_for(
                    lambda c=corpse: c.preempt_report is not None,
                    timeout=30), corpse.tail()
                assert corpse.preempt_report.get("snapshot")
                # exact per-element abandon accounting in the report
                abandoned = corpse.preempt_report.get("abandoned")
                assert isinstance(abandoned, dict)
                assert all(int(v) >= 0 for v in abandoned.values())
            # both ledgers balance exactly across kills + resurrections
            check_identities(rt.stats.snapshot(),
                             names=["router-settlement"])
            auto.check()
            life = auto.lifecycle()
            assert life["resurrections"] == n_kills
            assert rt.stats.snapshot()["router_requests"] == \
                n_clients * n_frames
        finally:
            for c in clients:
                try:
                    c["in"].end_stream()
                    c.stop()
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
            auto.stop()
            rp.stop()
            broker.stop()
        auto.check()  # stop() retired the fleet through the same ledger

    def test_blue_green_swap_mid_traffic(self, tmp_path):
        """A rollout under continuous client traffic: the ring converges
        on the new version with zero declared loss and the router
        settlement identity intact."""
        broker = DiscoveryBroker(port=0)
        broker.start()
        topic = "elastic-bg"
        rp = self._router(broker, topic)
        rt = rp["rt"]
        spec = ReplicaSpec(
            desc_template=_serve_desc(broker.bound_port, topic,
                                      with_version=True),
            ckpt_root=str(tmp_path / "ckpt"), grace_s=1.5,
            prelude=PRELUDE, version="blue")
        auto = Autoscaler(
            spec, router=rt,
            config=AutoscalerConfig(min_replicas=2, max_replicas=4,
                                    interval_s=0.1, target_delay_ms=1e6),
            name="bg")
        c = None
        pusher_stop = threading.Event()
        pushed = [0]
        try:
            auto.start()
            assert _wait_for(
                lambda: len(rt.router.replica_keys()) >= 2, timeout=60)
            c = _mk_client(rt.bound_port)

            def pusher():
                while not pusher_stop.is_set() and pushed[0] < 400:
                    _push4(c, [pushed[0]])
                    pushed[0] += 1
                    time.sleep(0.01)

            t = threading.Thread(target=pusher, daemon=True)
            t.start()
            assert _wait_for(lambda: _settled(c) >= 10, timeout=60)

            res = BlueGreenRollout(auto, "green",
                                   routable_timeout_s=60.0).run()
            assert res["replaced"] == 2

            pusher_stop.set()
            t.join(timeout=10)
            assert _wait_for(lambda: _settled(c) >= pushed[0], timeout=60)

            # the whole serving ring is green
            states = auto.replicas()
            assert sorted(states.values()) == [SERVING, SERVING]
            for ident in states:
                assert auto.handle(ident).version == "green"
            # ...and the router's replica loads agree (PONG carries the
            # version the replica was spawned with)
            live = [v for v in rt.router_report().values()
                    if v["state"] == "healthy"]
            assert live and all(
                v["load"].get("version") == "green" for v in live)

            st = c["qc"].stats.snapshot()
            got = [float(b.chunks[0].host()[0]) for b in c["out"].buffers]
            assert len(got) + st["shed"] == pushed[0]
            assert st["session_declared_lost"] == 0  # zero-downtime
            assert len(got) == len(set(got))
            check_identities(rt.stats.snapshot(),
                             names=["router-settlement"])
            auto.check()
        finally:
            pusher_stop.set()
            if c is not None:
                try:
                    c["in"].end_stream()
                    c.stop()
                except Exception:  # noqa: BLE001 — teardown best-effort
                    pass
            auto.stop()
            rp.stop()
            broker.stop()

    def test_warm_start_first_frame_within_2x(self, tmp_path):
        """The compile cache earns its keep: a warmed replica's first
        frame lands within 2x its steady state, while the cold control
        arm pays the jit compile on frame one."""
        desc = ("tensor_serve_src name=src port={port} id=91 buckets=1 "
                "max-wait-ms=2 "
                "! tensor_filter framework=jax model=zoo://mlp "
                "! tensor_serve_sink id=91")

        def run_life(spec, ident, n=20):
            rp = ReplicaProcess(spec, ident)
            rp.spawn()
            port = rp.wait_ready()
            c = parse_launch(
                f'appsrc name=in caps="{CAPS64}" '
                f"! tensor_query_client name=qc port={port} timeout=30 "
                "max-request=2 ! appsink name=out")
            c.start()
            lat = []
            try:
                for i in range(n):
                    n0 = len(c["out"].buffers)
                    t0 = time.perf_counter()
                    c["in"].push_buffer(Buffer.from_arrays(
                        [np.full(64, float(i), np.float32)]))
                    assert _wait_for(
                        lambda: len(c["out"].buffers) > n0, timeout=60)
                    lat.append(time.perf_counter() - t0)
            finally:
                c["in"].end_stream()
                c.stop()
                rp.preempt()
            return lat

        # JAX's own persistent cache is placed from outside, per arm:
        # the cold arm must not find the program a previous test run
        # left in <root>/.jax_cache
        def xla(d):
            return {"JAX_COMPILATION_CACHE_DIR": str(tmp_path / d)}

        cold_spec = ReplicaSpec(desc_template=desc,
                                ckpt_root=str(tmp_path / "ck-cold"),
                                env=xla("xla-cold"))
        warm_spec = ReplicaSpec(desc_template=desc,
                                ckpt_root=str(tmp_path / "ck-warm"),
                                compile_cache=str(tmp_path / "cc"),
                                env=xla("xla-warm"))

        cold = run_life(cold_spec, "cold-1")
        seed = run_life(warm_spec, "warm-0")  # records the signature
        cc = CompileCache(str(tmp_path / "cc"))
        assert cc.signatures("jax", "zoo://mlp|mesh=")  # registry wrote
        warm = run_life(warm_spec, "warm-1")  # fresh process, warm cache

        def steady(lat):
            mid = sorted(lat[5:])
            return mid[len(mid) // 2]

        # 50ms floor absorbs scheduler jitter on a loaded CI box; the
        # signal is the compile gap, which is far larger than that
        budget = max(2.0 * steady(warm), 0.05)
        assert warm[0] <= budget, (warm[0], steady(warm), cold[0])
        # the control arm proves the gap exists at all: a cold first
        # frame pays the trace+compile the warmed replica skipped
        assert cold[0] > budget, (cold[0], warm[0], budget)
        assert cold[0] > 2.0 * steady(cold)
        del seed
