"""SLO-driven elastic fleet: autoscaler policy, supervisor scale levers,
the drain/scale race, warming unroutability, and rendezvous re-homing.

Covers the PR's serving acceptance criteria:

- the policy's hysteresis bands: a scale-up needs the whole burn window
  saturated on every replica, a scale-down needs the whole (longer) idle
  window quiet, and cooldown/bounds hold everything else;
- the supervisor's ``scale_up``/``scale_down`` levers really add and drain
  processes, and a rolling drain cancels every scale action requested
  after it began (the SIGTERM race regression, driven by a scripted
  policy);
- a cold replica is registered but unroutable until its compile warmup
  completes (healthz "warming" 503 — the router never routes to it);
- rendezvous re-homing is bounded: growing or shrinking the fleet moves
  only the added/departed replica's tenants.

The full 1→2→1 resize under live HTTP load runs as a scripts/smoke_test.sh
stage; here the execution pipeline is
drilled with cheap sleeper processes so tier-1 stays fast.
"""

import asyncio
import json
import os
import sys
import threading
import time

import pytest

from relora_tpu.obs.fleet import SeriesStore
from relora_tpu.serve.autoscale import (
    ACTIVE_SLOTS_SERIES,
    MAX_BATCH_SERIES,
    QUEUE_DEPTH_SERIES,
    TTFT_P95_SERIES,
    UP_SERIES,
    Autoscaler,
    AutoscalerPolicy,
    Decision,
)
from relora_tpu.serve.router import rendezvous_home
from relora_tpu.serve.server import GenerateServer
from relora_tpu.serve.supervisor import ReplicaSupervisor
from tests.test_router import _FakeReplica

pytestmark = pytest.mark.autoscale

T0 = 1_000_000.0

#: a replica stand-in that binds nothing and exits 0 on SIGTERM — the
#: supervisor appends --port-file args, which a -c script ignores
SLEEPER = [
    sys.executable,
    "-c",
    "import signal,sys,time;"
    "signal.signal(signal.SIGTERM, lambda *a: sys.exit(0));"
    "time.sleep(600)",
]


def feed(store, source, series, values, t0=T0, dt=1.0):
    for i, v in enumerate(values):
        store.add_sample(source, series, float(v), t=t0 + i * dt)


def make_policy(**kw):
    base = dict(
        min_replicas=1,
        max_replicas=4,
        burn_window_s=5.0,
        idle_window_s=10.0,
        cooldown_s=10.0,
        min_samples=3,
    )
    base.update(kw)
    return AutoscalerPolicy(**base)


# -- policy hysteresis --------------------------------------------------------


def test_policy_scales_up_on_sustained_queue_burn():
    store, policy = SeriesStore(), make_policy()
    now = T0 + 4
    for rid in ("r0", "r1"):
        feed(store, rid, QUEUE_DEPTH_SERIES, [8, 9, 8, 10, 8])
    d = policy.decide(store, ["r0", "r1"], 2, now=now)
    assert d.action == "up" and "queue_depth" in d.reason


def test_policy_single_hot_replica_holds():
    """One saturated replica out of two is a routing story, not a capacity
    story — the fleet holds."""
    store, policy = SeriesStore(), make_policy()
    now = T0 + 4
    feed(store, "r0", QUEUE_DEPTH_SERIES, [8, 9, 8, 10, 8])
    feed(store, "r1", QUEUE_DEPTH_SERIES, [0, 0, 0, 0, 0])
    d = policy.decide(store, ["r0", "r1"], 2, now=now)
    assert d.action == "hold" and d.reason == "partial_burn"


def test_policy_brief_spike_does_not_scale():
    """A spike that does not fill the burn window (or fewer samples than
    min_samples) holds — flap resistance is structural."""
    store, policy = SeriesStore(), make_policy()
    now = T0 + 4
    feed(store, "r0", QUEUE_DEPTH_SERIES, [0, 0, 0, 9, 9])  # not sustained
    d = policy.decide(store, ["r0"], 1, now=now)
    assert d.action == "hold"

    store2 = SeriesStore()
    feed(store2, "r0", QUEUE_DEPTH_SERIES, [9, 9], t0=now - 1, dt=0.5)
    d = make_policy().decide(store2, ["r0"], 1, now=now)
    assert d.action == "hold"  # two samples < min_samples


def test_policy_ttft_and_slot_util_signals():
    store, policy = SeriesStore(), make_policy(ttft_p95_target_s=2.0, slot_util_high=0.9)
    now = T0 + 4
    feed(store, "r0", TTFT_P95_SERIES, [3.0, 2.5, 4.0, 3.2, 2.9])
    d = policy.decide(store, ["r0"], 1, now=now)
    assert d.action == "up" and "ttft_p95" in d.reason

    store2 = SeriesStore()
    feed(store2, "r0", ACTIVE_SLOTS_SERIES, [4, 4, 4, 4, 4])
    feed(store2, "r0", MAX_BATCH_SERIES, [4, 4, 4, 4, 4])
    d = make_policy().decide(store2, ["r0"], 1, now=now)
    assert d.action == "up" and "slot_utilization" in d.reason


def test_policy_respects_max_replicas():
    store, policy = SeriesStore(), make_policy(max_replicas=2)
    now = T0 + 4
    for rid in ("r0", "r1"):
        feed(store, rid, QUEUE_DEPTH_SERIES, [8, 9, 8, 10, 8])
    d = policy.decide(store, ["r0", "r1"], 2, now=now)
    assert d.action == "hold" and d.reason == "at_max_replicas"


def test_policy_scales_down_on_sustained_idle_only():
    store, policy = SeriesStore(), make_policy()
    now = T0 + 9  # idle window covers t0..t0+9
    for rid in ("r0", "r1"):
        feed(store, rid, QUEUE_DEPTH_SERIES, [0] * 10)
        feed(store, rid, ACTIVE_SLOTS_SERIES, [0] * 10)
        feed(store, rid, MAX_BATCH_SERIES, [4] * 10)
    d = policy.decide(store, ["r0", "r1"], 2, now=now)
    assert d.action == "down" and d.reason == "sustained_idle"

    # at the floor, idle holds instead
    d = policy.decide(store, ["r0", "r1"], 1, now=now)
    assert d.action == "hold" and d.reason == "at_min_replicas"

    # one queued sample inside the window cancels the drain
    store.add_sample("r1", QUEUE_DEPTH_SERIES, 2.0, t=now - 1.0)
    d = make_policy().decide(store, ["r0", "r1"], 2, now=now)
    assert d.action == "hold"


def test_policy_cooldown_gates_consecutive_actions():
    store, policy = SeriesStore(), make_policy(cooldown_s=10.0)
    now = T0 + 4
    feed(store, "r0", QUEUE_DEPTH_SERIES, [8, 9, 8, 10, 8])
    assert policy.decide(store, ["r0"], 1, now=now).action == "up"
    policy.note_scaled(now)
    d = policy.decide(store, ["r0"], 2, now=now + 5)
    assert d.action == "hold" and d.reason == "cooldown"
    # cooldown expired and the burn persists: acts again
    feed(store, "r0", QUEUE_DEPTH_SERIES, [8, 9, 8, 10, 8], t0=now + 7)
    assert policy.decide(store, ["r0"], 2, now=now + 11).action == "up"


# -- executor -----------------------------------------------------------------


class FakeSupervisor:
    def __init__(self, n=1):
        self.n = n
        self.calls = []
        self.draining = False

    def endpoints(self):
        return {f"r{i}": ("127.0.0.1", 8000 + i) for i in range(self.n)}

    def n_live(self):
        return self.n

    def scale_up(self):
        if self.draining:
            return None
        self.calls.append("up")
        self.n += 1
        return f"r{self.n - 1}"

    def scale_down(self, idx=None):
        if self.draining or self.n <= 1:
            return None
        self.calls.append("down")
        self.n -= 1
        return f"r{self.n}"


class ScriptedPolicy:
    """Fixed decision per step — isolates the executor from the bands."""

    def __init__(self, decisions):
        self.decisions = list(decisions)
        self.scaled_at = []

    def decide(self, store, sources, n_live, now=None):
        return (
            self.decisions.pop(0)
            if self.decisions
            else Decision("hold", "steady", {"n_live": n_live})
        )

    def note_scaled(self, now=None):
        self.scaled_at.append(now)


def test_autoscaler_executes_decisions_and_records_events():
    store = SeriesStore()
    sup = FakeSupervisor(n=1)
    feed(store, "r0", UP_SERIES, [1.0], t0=T0)
    policy = ScriptedPolicy(
        [
            Decision("up", "sustained_burn (queue_depth)"),
            Decision("hold", "cooldown"),
            Decision("hold", "cooldown"),  # duplicate hold: one event only
            Decision("down", "sustained_idle"),
        ]
    )
    asc = Autoscaler(policy, sup, store)
    feed(store, "r1", UP_SERIES, [1.0], t0=T0)  # new replica reports up
    for i in range(4):
        asc.step(now=T0 + i)
    assert sup.calls == ["up", "down"]
    assert len(policy.scaled_at) == 2
    events = store.events(kinds=("autoscale_decision",))
    actions = [e["action"] for e in events]
    assert actions == ["up", "hold", "down"]  # the duplicate hold collapsed
    # replica-count series sampled every step
    assert [v for _, v in store.samples("autoscaler", "replicas_live")] == [
        1.0, 2.0, 2.0, 2.0,
    ]


def test_autoscaler_holds_scale_up_while_replica_warming():
    """Capacity that cannot be routed to yet (healthz "warming" → up == 0)
    must not count as capacity — the executor refuses to stack scale-ups."""
    store = SeriesStore()
    sup = FakeSupervisor(n=2)
    feed(store, "r0", UP_SERIES, [1.0], t0=T0)
    feed(store, "r1", UP_SERIES, [0.0], t0=T0)  # still warming
    policy = ScriptedPolicy([Decision("up", "sustained_burn (queue_depth)")])
    d = Autoscaler(policy, sup, store).step(now=T0 + 1)
    assert d.action == "hold" and d.reason == "replica_warming"
    assert d.metrics["warming"] == "r1"
    assert sup.calls == []


# -- supervisor scale levers (real processes) ---------------------------------


def _events_sink():
    events = []
    lock = threading.Lock()

    def on_event(event, idx, detail):
        with lock:
            events.append((event, idx, dict(detail)))

    return events, on_event


def test_supervisor_scale_up_down_lifecycle(tmp_path):
    events, on_event = _events_sink()
    sup = ReplicaSupervisor(
        SLEEPER, 1, str(tmp_path),
        drain_timeout_s=10.0, poll_interval_s=0.05, on_event=on_event,
    )
    sup.start()
    try:
        assert sup.n_live() == 1
        rid = sup.scale_up()
        assert rid == "r1"
        assert set(sup.endpoints()) == {"r0", "r1"}
        assert sup.n_live() == 2
        assert sup.status()["r1"]["running"]
        time.sleep(0.5)  # let the sleeper install its SIGTERM handler

        # newest drains first; the fleet never treats its exit as a crash
        assert sup.scale_down() == "r1"
        assert set(sup.endpoints()) == {"r0"}
        assert sup.n_live() == 1
        # the floor: never drain the last replica
        assert sup.scale_down() is None
        time.sleep(0.3)  # a few monitor rounds
        kinds = [e[0] for e in events]
        assert "autoscale_up" in kinds and "autoscale_down_complete" in kinds
        assert "crash" not in kinds
        down_done = next(e for e in events if e[0] == "autoscale_down_complete")
        assert down_done[2]["exit_code"] == 0  # clean SIGTERM exit
        # freed indices are never reused: the next scale-up is r2, so a
        # stale port file can never be routed to
        assert sup.scale_up() == "r2"
    finally:
        sup.stop()


def test_rolling_drain_cancels_pending_scale_up(tmp_path):
    """The SIGTERM race regression: a scale-up decided while the rolling
    drain runs must be cancelled, not spawn a process the drain will never
    visit."""
    events, on_event = _events_sink()
    sup = ReplicaSupervisor(
        SLEEPER, 2, str(tmp_path),
        drain_timeout_s=10.0, poll_interval_s=0.05, on_event=on_event,
    )
    sup.start()
    try:
        drainer = threading.Thread(target=sup.begin_rolling_drain, daemon=True)
        drainer.start()
        # the drain flag flips before the drain starts touching processes;
        # from that instant every scale action must refuse
        deadline = time.monotonic() + 5.0
        while not sup._draining and time.monotonic() < deadline:
            time.sleep(0.005)
        assert sup._draining
        assert sup.scale_up() is None  # blocks on the scale lock, then cancels
        assert sup.scale_down() is None
        drainer.join(15.0)
        assert not drainer.is_alive()
        kinds = [e[0] for e in events]
        assert "autoscale_up_cancelled" in kinds
        assert kinds.count("drain_complete") == 2
        # nothing was spawned after the drain began
        assert not any(k == "autoscale_up" for k in kinds)
        assert all(not st["running"] for st in sup.status().values())
    finally:
        sup.stop()


def test_scripted_autoscaler_refuses_during_drain(tmp_path):
    """Same race through the executor: a scripted always-up policy stepping
    concurrently with the drain ends in a cancelled decision, never a new
    replica."""
    store = SeriesStore()
    sup = ReplicaSupervisor(
        SLEEPER, 2, str(tmp_path), drain_timeout_s=10.0, poll_interval_s=0.05
    )
    sup.start()
    try:
        for rid in ("r0", "r1"):
            feed(store, rid, UP_SERIES, [1.0], t0=time.time())
        policy = ScriptedPolicy(
            [Decision("up", "sustained_burn (queue_depth)")] * 3
        )
        asc = Autoscaler(policy, sup, store)
        drainer = threading.Thread(target=sup.begin_rolling_drain, daemon=True)
        drainer.start()
        deadline = time.monotonic() + 5.0
        while not sup._draining and time.monotonic() < deadline:
            time.sleep(0.005)
        d = asc.step()
        assert d.action == "hold" and d.reason == "scale_up_cancelled"
        assert policy.scaled_at == []  # no cooldown burned on a cancel
        drainer.join(15.0)
        assert sup.n_live() == 0 or all(
            not st["running"] for st in sup.status().values()
        )
    finally:
        sup.stop()


# -- rendezvous re-homing (property: bounded churn) ---------------------------


def test_rendezvous_rehoming_moves_only_the_changed_replicas_tenants():
    adapters = [f"tenant-{i}" for i in range(64)]
    groups = [f"r{i}" for i in range(4)]
    before = {a: rendezvous_home(a, groups) for a in adapters}
    # every group homes someone (64 tenants over 4 groups)
    assert set(before.values()) == set(groups)

    # grow: the only tenants that move are the ones landing on the new group
    grown = groups + ["r4"]
    after_grow = {a: rendezvous_home(a, grown) for a in adapters}
    moved = {a for a in adapters if after_grow[a] != before[a]}
    assert moved  # statistically certain: E[|moved|] = 64/5
    assert all(after_grow[a] == "r4" for a in moved)

    # shrink: only the departed group's tenants move, everyone else stays
    shrunk = [g for g in groups if g != "r2"]
    after_shrink = {a: rendezvous_home(a, shrunk) for a in adapters}
    for a in adapters:
        if before[a] == "r2":
            assert after_shrink[a] in shrunk
        else:
            assert after_shrink[a] == before[a]

    # the home is a pure function of the *set* of groups, not their order
    assert all(
        rendezvous_home(a, list(reversed(grown))) == after_grow[a] for a in adapters
    )
    assert rendezvous_home("anyone", []) is None


# -- warming: discoverable but unroutable until warmup completes --------------


class _IdleScheduler:
    """The minimum scheduler surface GenerateServer drives when no requests
    arrive — warming is decided on the model thread before the first real
    scheduler interaction, so nothing else is needed."""

    max_batch = 4
    active_slots = 0
    queue_depth = 0

    def __init__(self):
        from relora_tpu.obs.tracer import NoopTracer

        self.tracer = NoopTracer()
        self.obs_registry = None

    def publish_constants(self):
        pass

    def drop_host_gap(self):
        pass

    def has_work(self):
        return False

    def step(self):
        pass

    def cancel(self, uid):
        pass

    def fail_all(self, reason="", detail=""):
        pass


def test_server_warming_healthz_until_warmup_completes():
    """A replica with a pending warmup binds its listener (discoverable)
    but answers healthz 503 "warming"; completion of warmup_fn promotes it
    to 200 "ok" and publishes the warmup report."""
    from tests.test_server import _http as server_http

    release = threading.Event()

    def warmup():
        assert release.wait(30), "warmup never released"
        return {"buckets": 1}

    server = GenerateServer(_IdleScheduler(), port=0, max_queue=4, warmup_fn=warmup)
    thread = threading.Thread(
        target=lambda: asyncio.run(
            server.serve_forever(install_signal_handlers=False)
        ),
        daemon=True,
    )
    thread.start()
    try:
        assert server.started.wait(30), "listener never bound"
        # the port is live before warmup finishes — but not routable
        status, _, body = server_http(server.port, "GET", "/healthz")
        payload = json.loads(body)
        assert status == 503
        assert payload["status"] == "warming"
        assert payload["detail"] == "compile warmup in progress"

        release.set()
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            status, _, body = server_http(server.port, "GET", "/healthz")
            if status == 200:
                break
            time.sleep(0.02)
        assert status == 200 and json.loads(body)["status"] == "ok"
        assert server.warmup_report == {"buckets": 1}
    finally:
        release.set()
        server.begin_drain()
        thread.join(30)
    assert not thread.is_alive(), "server did not drain"
    assert server._worker_error is None, repr(server._worker_error)


class _WarmingReplica(_FakeReplica):
    """A _FakeReplica whose healthz answers 503 "warming" until the test
    flips ``warming`` off — the serve.py cold-start shape."""

    def __init__(self, **kw):
        self.warming = True
        super().__init__(**kw)

    async def _respond_healthz(self, writer):
        if not self.warming:
            await super()._respond_healthz(writer)
            return
        body = json.dumps(
            {"status": "warming", "detail": "compile warmup in progress"}
        ).encode()
        writer.write(
            f"HTTP/1.1 503 X\r\nContent-Type: application/json\r\n"
            f"Content-Length: {len(body)}\r\nConnection: close\r\n\r\n".encode()
            + body
        )
        await writer.drain()


def test_router_never_routes_to_warming_replica():
    """With one warm and one warming replica, every request lands on the
    warm one; the warming replica is adopted only after its healthz clears."""
    from tests.test_router import _RouterHarness, _http as router_http

    warm, cold = _FakeReplica(), _WarmingReplica()
    harness = _RouterHarness(
        {"warm": ("127.0.0.1", warm.port), "cold": ("127.0.0.1", cold.port)},
        probe_interval_s=0.05,
    )
    try:
        with harness as router:
            harness.wait_healthy(1)
            # the prober reaches the replicas one after another: wait until
            # it has been to the cold one instead of assuming it was first
            deadline = time.monotonic() + 10.0
            while router.replicas["cold"].status != "warming" and time.monotonic() < deadline:
                time.sleep(0.01)
            assert router.replicas["cold"].status == "warming"
            assert router.replicas["cold"].healthy is False
            for _ in range(6):
                status, headers, _ = router_http(
                    router.port, "POST", "/v1/generate",
                    {"prompt": [1], "max_new_tokens": 2},
                )
                assert status == 200
                assert headers["x-relora-replica"] == "warm"
            assert cold.gen_hits == 0  # zero traffic into the compile stall

            cold.warming = False  # warmup completes -> healthz 200
            harness.wait_healthy(2)
            deadline = time.monotonic() + 10.0
            while cold.gen_hits == 0 and time.monotonic() < deadline:
                router_http(
                    router.port, "POST", "/v1/generate",
                    {"prompt": [1], "max_new_tokens": 2},
                )
            assert cold.gen_hits > 0  # promoted replica now takes traffic
    finally:
        warm.close()
        cold.close()
