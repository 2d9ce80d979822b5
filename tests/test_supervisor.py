"""Fleet supervisor (ISSUE 16): the /scale actuation loop against a
fake process table (hysteresis, cooldown, replacement outside the
gates, spawn-failure cleanup, crash-only adoption, advisory-only
degradation), device-second admission pricing (few-huge and many-tiny
tenants throttled equivalently; fleet-median fallback for unknown
buckets), SLO-class lease weights changing the deficit-WRR order
under contention, the /scale non-draining capacity clamp, the fleet
report's Supervisor timeline, and lint check 16."""

import io
import json
import os
import signal
import time

import pytest

from presto_tpu.obs import slo
from presto_tpu.serve import supervisor as suplib
from presto_tpu.serve.jobledger import JobLedger, TenantQuotaExceeded
from presto_tpu.serve.supervisor import (DRAINING, SPAWNING, UP,
                                         FleetSupervisor,
                                         SupervisorConfig,
                                         load_registry)


def _row(tenant="t", job="j1", ts=0.0, state="done", execute=1.0,
         bucket="b"):
    return {"tenant": tenant, "job_id": job, "ts": ts,
            "state": state, "bucket": bucket,
            "phases": {"execute": execute, "total": execute}}


# ----------------------------------------------------------------------
# the decision machine against a fake process table
# ----------------------------------------------------------------------

class FakeSup(FleetSupervisor):
    """FleetSupervisor whose process seams hit an in-memory table:
    `table[name] = pid` is a live process, absent is dead.  SIGKILL
    removes the entry (kill -9 semantics); SIGTERM only records, the
    test decides when the 'process' exits."""

    def __init__(self, cfg, table=None):
        super().__init__(cfg)
        self.table = {} if table is None else table
        self.signals = []
        self._next_pid = 1000

    def _popen(self, name, argv):
        self._next_pid += 1
        self.table[name] = self._next_pid
        return self._next_pid

    def _alive(self, name, pid):
        return pid is not None and self.table.get(name) == pid

    def _signal(self, name, pid, sig):
        self.signals.append((name, sig))
        if sig == signal.SIGKILL:
            self.table.pop(name, None)

    def _reap(self, name):
        pass


def _mksup(tmp_path, table=None, **kw):
    kw.setdefault("scale_up_after", 2)
    kw.setdefault("scale_down_after", 2)
    kw.setdefault("cooldown_s", 5.0)
    kw.setdefault("min_replicas", 1)
    kw.setdefault("max_replicas", 4)
    kw.setdefault("heartbeat_timeout", 10.0)
    sup = FakeSup(SupervisorConfig(
        fleetdir=str(tmp_path), router_url="http://x", **kw),
        table=table)
    sup.advice = {"wanted_replicas": 1, "reason": "test",
                  "inputs": {"backlog_jobs": 0}}
    sup._fetch_advice = lambda: sup.advice
    return sup


def _events(tmp_path):
    out = []
    with open(suplib.events_path(str(tmp_path))) as f:
        for ln in f:
            if ln.strip():
                out.append(json.loads(ln))
    return out


def test_spawn_waits_for_hysteresis_then_confirms_up(tmp_path):
    sup = _mksup(tmp_path)
    d = sup.step(now=0.0)
    assert d["action"] == "hold" and "hysteresis" in d["why"]
    d = sup.step(now=1.0)
    assert d["action"] == "spawn" and len(d["replicas"]) == 1
    name = d["replicas"][0]
    assert sup.replicas()[name]["state"] == SPAWNING
    # the first ledger heartbeat confirms the replica UP
    sup.ledger.heartbeat(name, 0, now=1.5)
    d = sup.step(now=2.0)
    assert d["action"] == "steady"
    assert sup.replicas()[name]["state"] == UP
    kinds = [e["kind"] for e in _events(tmp_path)]
    assert "supervisor-spawn" in kinds and "supervisor-up" in kinds


def test_device_replicas_get_one_chip_each_and_no_more(tmp_path,
                                                      monkeypatch):
    """One process per chip: each device-using replica is spawned
    confined to its own chip, and a spawn past the host's chip count
    is refused (not started to fail or hang at its first device
    call); a dead replica's chip goes to its replacement."""
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(suplib, "host_chips", lambda: 2)
    sup = _mksup(tmp_path, cooldown_s=0.0, scale_up_after=1)
    sup.advice = {"wanted_replicas": 3, "reason": "backlog",
                  "inputs": {}}
    for t in range(4):
        sup.step(now=float(t))
    rows = sup.replicas()
    assert sorted(r["chip"] for r in rows.values()) == [0, 1]
    refused = [e for e in _events(tmp_path)
               if e["kind"] == "supervisor-spawn-failed"]
    assert refused and "no free chip" in refused[0]["why"]
    # the spawn environment confines the process to its chip
    name = min(rows, key=lambda n: rows[n]["chip"])
    assert suplib.chip_env(rows[name]["chip"])["TPU_VISIBLE_CHIPS"] \
        == "0"
    # replica on chip 0 dies: its replacement takes chip 0 again
    for n in rows:
        sup.ledger.heartbeat(n, 0, now=4.5)
    sup.step(now=5.0)
    sup.table.pop(name)
    sup.step(now=6.0)
    assert sorted(r["chip"] for r in sup.replicas().values()) == [0, 1]


def test_cpu_replicas_need_no_chip(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    sup = _mksup(tmp_path)
    assert sup._chip_slots() is None
    sup.step(now=0.0)
    sup.step(now=1.0)
    assert all("chip" not in r for r in sup.replicas().values())


def test_cooldown_withholds_and_emits_hold_event(tmp_path):
    sup = _mksup(tmp_path)
    sup.step(now=0.0)
    sup.step(now=1.0)                      # spawn at t=1
    name = list(sup.replicas())[0]
    sup.ledger.heartbeat(name, 0, now=1.5)
    sup.advice = {"wanted_replicas": 3, "reason": "backlog",
                  "inputs": {}}
    d = sup.step(now=2.0)
    assert d["action"] == "hold" and "hysteresis" in d["why"]
    d = sup.step(now=3.0)                  # streak met, cooldown not
    assert d["action"] == "hold" and "cooldown" in d["why"]
    d = sup.step(now=7.0)                  # cooldown (5s) elapsed
    assert d["action"] == "spawn" and len(d["replicas"]) == 2
    holds = [e for e in _events(tmp_path)
             if e["kind"] == "supervisor-hold"]
    assert holds and all("why" in e and "wanted" in e
                         for e in holds)


def test_actuation_events_carry_advisory_inputs(tmp_path):
    sup = _mksup(tmp_path)
    sup.advice = {"wanted_replicas": 2, "reason": "backlog-drain",
                  "inputs": {"backlog_jobs": 7}}
    sup.step(now=0.0)
    sup.step(now=1.0)
    spawns = [e for e in _events(tmp_path)
              if e["kind"] == "supervisor-spawn"]
    assert spawns
    assert all(e["advice_reason"] == "backlog-drain"
               and e["inputs"]["backlog_jobs"] == 7
               and e["wanted"] == 2 for e in spawns)


def test_scale_down_drains_youngest_gracefully(tmp_path):
    sup = _mksup(tmp_path, cooldown_s=0.0)
    sup.advice = {"wanted_replicas": 3, "reason": "t", "inputs": {}}
    sup.step(now=0.0)
    sup.step(now=1.0)
    for name in sup.replicas():
        sup.ledger.heartbeat(name, 0, now=1.5)
    sup.step(now=2.0)
    assert all(r["state"] == UP for r in sup.replicas().values())
    sup.advice = {"wanted_replicas": 1, "reason": "idle",
                  "inputs": {}}
    sup.step(now=3.0)
    d = sup.step(now=4.0)
    assert d["action"] == "drain" and len(d["replicas"]) == 2
    draining = [n for n, r in sup.replicas().items()
                if r["state"] == DRAINING]
    assert sorted(draining) == sorted(d["replicas"])
    assert all((n, signal.SIGTERM) in sup.signals for n in draining)
    # the youngest (highest seq) replicas drain; the oldest stays
    assert min(sup.replicas()) not in draining
    # processes exit -> rows reaped from the registry
    for n in draining:
        sup.table.pop(n)
    sup.step(now=5.0)
    assert len(sup.replicas()) == 1
    kinds = [e["kind"] for e in _events(tmp_path)]
    assert kinds.count("supervisor-drained") == 2


def test_drain_timeout_escalates_to_sigkill(tmp_path):
    sup = _mksup(tmp_path, cooldown_s=0.0, drain_timeout_s=10.0)
    sup.advice = {"wanted_replicas": 2, "reason": "t", "inputs": {}}
    sup.step(now=0.0)
    sup.step(now=1.0)
    for name in sup.replicas():
        sup.ledger.heartbeat(name, 0, now=1.5)
    sup.step(now=2.0)
    sup.advice = {"wanted_replicas": 1, "reason": "idle",
                  "inputs": {}}
    sup.step(now=3.0)
    sup.step(now=4.0)                       # drain starts, deadline 14
    (victim,) = [n for n, r in sup.replicas().items()
                 if r["state"] == DRAINING]
    sup.step(now=20.0)                      # wedged past the deadline
    assert (victim, signal.SIGKILL) in sup.signals
    sup.step(now=21.0)                      # SIGKILL dropped it
    assert victim not in sup.replicas()
    kinds = [e["kind"] for e in _events(tmp_path)]
    assert "supervisor-drain-timeout" in kinds


def test_dead_replica_replaced_outside_the_gates(tmp_path):
    sup = _mksup(tmp_path, cooldown_s=100.0)
    sup.step(now=0.0)
    sup.step(now=1.0)                       # actuation at t=1
    (name,) = list(sup.replicas())
    sup.ledger.heartbeat(name, 0, now=1.5)
    sup.step(now=2.0)
    sup.table.pop(name)                     # kill -9
    # well inside the 100s cooldown: repair must not wait it out
    sup.step(now=3.0)
    reps = sup.replicas()
    assert name not in reps and len(reps) == 1
    ev = [e for e in _events(tmp_path)
          if e["kind"] == "supervisor-replace"]
    assert ev and ev[0]["replica"] == name and ev[0]["replacement"]


def test_wedged_replica_sigkilled_then_replaced(tmp_path):
    sup = _mksup(tmp_path, heartbeat_timeout=5.0)
    sup.step(now=0.0)
    sup.step(now=1.0)
    (name,) = list(sup.replicas())
    sup.ledger.heartbeat(name, 0, now=2.0)
    sup.step(now=3.0)
    assert sup.replicas()[name]["state"] == UP
    # process alive but the ledger heartbeat goes stale -> wedged
    sup.step(now=10.0)
    assert (name, signal.SIGKILL) in sup.signals
    assert name not in sup.replicas()
    assert len(sup.replicas()) == 1         # replacement spawned


def test_spawn_failure_cleans_registry_and_emits(tmp_path):
    sup = _mksup(tmp_path)

    def boom(name, argv):
        raise OSError("no such binary")
    sup._popen = boom
    sup.step(now=0.0)
    sup.step(now=1.0)
    assert sup.replicas() == {}
    assert load_registry(str(tmp_path))["replicas"] == {}
    ev = [e for e in _events(tmp_path)
          if e["kind"] == "supervisor-spawn-failed"]
    assert ev and "no such binary" in ev[0]["why"]


def test_spawn_deadline_kills_silent_child(tmp_path):
    sup = _mksup(tmp_path, spawn_timeout_s=30.0)
    sup.step(now=0.0)
    sup.step(now=1.0)
    (name,) = list(sup.replicas())
    # never heartbeats; past the deadline the child is killed
    sup.step(now=40.0)
    assert (name, signal.SIGKILL) in sup.signals
    ev = [e for e in _events(tmp_path)
          if e["kind"] == "supervisor-spawn-failed"]
    assert ev and "no heartbeat" in ev[0]["why"]


def test_advisory_unreachable_holds_without_acting(tmp_path):
    sup = _mksup(tmp_path)
    sup._fetch_advice = lambda: None
    for t in (0.0, 1.0, 2.0):
        d = sup.step(now=t)
        assert d["action"] == "hold"
        assert d["why"] == "advisory-unreachable"
    assert sup.replicas() == {}


def test_stop_leaves_replicas_running(tmp_path):
    sup = _mksup(tmp_path)
    sup.step(now=0.0)
    sup.step(now=1.0)
    (name,) = list(sup.replicas())
    sup.stop()
    # no signal of any kind was sent: the fleet degrades to
    # advisory-only, the registry persists for the next supervisor
    assert sup.signals == []
    assert name in sup.table
    assert name in load_registry(str(tmp_path))["replicas"]


def test_restarted_supervisor_adopts_survivors(tmp_path):
    table = {}
    sup = _mksup(tmp_path, table=table)
    sup.advice = {"wanted_replicas": 2, "reason": "t", "inputs": {}}
    sup.step(now=0.0)
    sup.step(now=1.0)
    names = sorted(sup.replicas())
    assert len(names) == 2
    # the supervisor dies abruptly; one replica dies with it
    table.pop(names[0])
    sup2 = _mksup(tmp_path, table=table)
    adopted = sup2.adopt(now=10.0)
    assert adopted == [names[1]]
    assert sorted(sup2.replicas()) == [names[1]]
    # the dead row was dropped from the persisted registry too
    assert sorted(load_registry(str(tmp_path))["replicas"]) \
        == [names[1]]
    # nothing spawned anew for the adopted replica
    assert [e["replica"] for e in _events(tmp_path)
            if e["kind"] == "supervisor-adopt"] == [names[1]]


def test_registry_survives_reload_roundtrip(tmp_path):
    sup = _mksup(tmp_path)
    sup.step(now=0.0)
    sup.step(now=1.0)
    reg = load_registry(str(tmp_path))
    assert reg["version"] == suplib.REGISTRY_VERSION
    (row,) = reg["replicas"].values()
    assert row["state"] == SPAWNING and row["pid"] is not None
    # unreadable/garbage registry degrades to empty, never raises
    with open(suplib.registry_path(str(tmp_path)), "w") as f:
        f.write("{half a json")
    assert load_registry(str(tmp_path))["replicas"] == {}


# ----------------------------------------------------------------------
# device-second admission pricing
# ----------------------------------------------------------------------

def _priced_ledger(tmp_path, monkeypatch):
    from presto_tpu.obs import Observability, ObsConfig
    monkeypatch.setenv("PRESTO_TPU_USAGE", "1")
    led = JobLedger(str(tmp_path),
                    obs=Observability(ObsConfig(enabled=True)))
    for i in range(3):
        led.usage.append(_row(job="h%d" % i, bucket="huge",
                              execute=10.0))
        led.usage.append(_row(job="t%d" % i, bucket="tiny",
                              execute=1.0))
    return led


def test_ds_quota_throttles_per_device_second(tmp_path, monkeypatch):
    """A tenant of few huge jobs and one of many tiny jobs hit the
    same ds_quota at the same expected device-seconds — the pricing
    is per device-second, not per job."""
    led = _priced_ledger(tmp_path, monkeypatch)
    led.set_tenant("A", ds_quota=20.0)
    led.set_tenant("B", ds_quota=20.0)
    spec = {"rawfiles": ["x"], "config": {}}
    for _ in range(2):                      # 2 x 10s = 20 dev-s
        led.admit(spec, tenant="A", bucket="huge")
    with pytest.raises(TenantQuotaExceeded) as e:
        led.admit(spec, tenant="A", bucket="huge")
    assert e.value.unit == "device-seconds"
    assert e.value.cost == pytest.approx(10.0)
    for _ in range(20):                     # 20 x 1s = 20 dev-s
        led.admit(spec, tenant="B", bucket="tiny")
    with pytest.raises(TenantQuotaExceeded) as e:
        led.admit(spec, tenant="B", bucket="tiny")
    assert e.value.unit == "device-seconds"
    # the rejection landed on the flight recorder, typed
    ev = [e for e in led.obs.flightrec.records()
          if e["kind"] == "quota-exceeded"]
    assert ev and all(e["unit"] == "device-seconds" for e in ev)


def test_unknown_bucket_priced_at_fleet_median(tmp_path,
                                               monkeypatch):
    led = _priced_ledger(tmp_path, monkeypatch)
    est = led.cost_estimator()
    assert est("huge") == pytest.approx(10.0)
    assert est("tiny") == pytest.approx(1.0)
    assert est("never-seen") == pytest.approx(5.5)   # median fallback
    led.set_tenant("C", ds_quota=10.0)
    spec = {"rawfiles": ["x"], "config": {}}
    led.admit(spec, tenant="C", bucket="never-seen")
    with pytest.raises(TenantQuotaExceeded):         # 5.5+5.5 > 10
        led.admit(spec, tenant="C", bucket="never-seen")


def test_fleet_median_default_when_no_usage():
    assert slo.fleet_median_cost({}, default_s=7.0) == 7.0
    assert slo.fleet_median_cost({"a": 4.0}, default_s=7.0) == 4.0


def test_count_quota_keeps_unit_jobs(tmp_path, monkeypatch):
    led = _priced_ledger(tmp_path, monkeypatch)
    led.set_tenant("D", quota=1)
    spec = {"rawfiles": ["x"], "config": {}}
    led.admit(spec, tenant="D", bucket="tiny")
    with pytest.raises(TenantQuotaExceeded) as e:
        led.admit(spec, tenant="D", bucket="tiny")
    assert e.value.unit == "jobs"


def test_backlog_device_seconds_prices_active_rows(tmp_path,
                                                   monkeypatch):
    led = _priced_ledger(tmp_path, monkeypatch)
    spec = {"rawfiles": ["x"], "config": {}}
    led.admit(spec, bucket="huge")
    led.admit(spec, bucket="tiny")
    assert led.backlog_device_seconds() == pytest.approx(11.0)


# ----------------------------------------------------------------------
# SLO-class lease weights
# ----------------------------------------------------------------------

def test_slo_class_weights_from_specs(tmp_path):
    led = JobLedger(str(tmp_path))
    assert led._class_weights() == {}
    slo.save_specs(str(tmp_path), [slo.parse_spec("gold:0.999"),
                                   slo.parse_spec("bronze:0.5")])
    w = led._class_weights()
    assert w["gold"] == pytest.approx(100.0)   # capped at 100
    assert w["bronze"] == pytest.approx(2.0)
    # stat-keyed cache invalidates when the specs change
    time.sleep(0.01)
    slo.save_specs(str(tmp_path), [slo.parse_spec("gold:0.9")])
    assert led._class_weights() == {"gold": pytest.approx(10.0)}


def test_slo_class_weights_change_lease_order(tmp_path):
    """Under contention, declaring an SLO IS declaring lease
    priority: with equal configured weights, the 99.9% tenant's jobs
    lease ahead of the 50% tenant's backfill."""
    led = JobLedger(str(tmp_path))
    slo.save_specs(str(tmp_path), [slo.parse_spec("gold:0.999"),
                                   slo.parse_spec("bronze:0.5")])
    spec = {"rawfiles": ["x"], "config": {}}
    for i in range(3):
        led.admit(spec, tenant="gold", bucket="b")
        led.admit(spec, tenant="bronze", bucket="b")
    order = []
    for _ in range(6):
        lease = led.lease("h", 30.0)
        order.append(led.view(lease.item_id)["tenant"])
    # deficit-WRR: one bronze may win the 0/0 tie, then gold's ~50x
    # class weight drains gold completely before bronze continues
    assert order.index("gold") <= 1
    assert order[order.index("gold"):][:3] == ["gold"] * 3
    # without specs the same setup would alternate: pin the contrast
    led2 = JobLedger(str(tmp_path / "plain"))
    for i in range(3):
        led2.admit(spec, tenant="gold", bucket="b")
        led2.admit(spec, tenant="bronze", bucket="b")
    order2 = [led2.view(led2.lease("h", 30.0).item_id)["tenant"]
              for _ in range(4)]
    assert order2[:4] == ["bronze", "gold", "bronze", "gold"]


# ----------------------------------------------------------------------
# /scale capacity clamps to ready non-draining replicas (satellite 4)
# ----------------------------------------------------------------------

def test_serving_replicas_excludes_draining(tmp_path):
    from presto_tpu.serve.router import FleetRouter, RouterConfig
    router = FleetRouter(RouterConfig(fleetdir=str(tmp_path)))
    with router._ready_lock:
        router._ready = {
            "a": {"ready": True},
            "b": {"ready": True, "draining": True},
            "c": {"ready": True, "lease": {"draining": True}},
            "d": {"ready": False},
        }
    assert router.serving_replicas() == ["a"]
    assert sorted(router.ready_replicas()) == ["a", "b", "c"]


# ----------------------------------------------------------------------
# the fleet report's Supervisor timeline
# ----------------------------------------------------------------------

def test_fleet_report_renders_supervisor_timeline(tmp_path):
    from presto_tpu.apps.report import collect_fleet, render_fleet
    fleetdir = str(tmp_path)
    JobLedger(fleetdir)                       # jobs.json exists
    with open(suplib.registry_path(fleetdir), "w") as f:
        json.dump({"version": 1, "seq": 1, "replicas": {
            "sup-0001": {"state": "up", "pid": 4242,
                         "spawned": 10.0}}}, f)
    with open(suplib.events_path(fleetdir), "w") as f:
        for ev in (
            {"kind": "supervisor-start", "ts": 9.0, "seq": 1},
            {"kind": "supervisor-spawn", "ts": 10.0, "seq": 2,
             "replica": "sup-0001", "wanted": 1,
             "advice_reason": "min-replicas"},
            {"kind": "supervisor-up", "ts": 12.5, "seq": 3,
             "replica": "sup-0001", "warmup_s": 2.5},
            {"kind": "supervisor-hold", "ts": 13.0, "seq": 4,
             "wanted": 2, "why": "hysteresis 1/2"},
        ):
            f.write(json.dumps(ev) + "\n")
    info = collect_fleet(fleetdir)
    assert info["supervisor"]["by_kind"]["supervisor-spawn"] == 1
    out = io.StringIO()
    render_fleet(info, file=out)
    text = out.getvalue()
    assert "Supervisor" in text
    assert "sup-0001" in text
    assert "spawn" in text and "min-replicas" in text
    assert "warmup=2.50s" in text
    assert "1 hold(s)" in text


# ----------------------------------------------------------------------
# preempt-fraction pacing (ISSUE 17: spot capacity as steady state)
# ----------------------------------------------------------------------

def test_preempt_kills_most_loaded_campaign_holder(tmp_path):
    """The pacer SIGKILLs (no drain) the replica holding the most
    campaign-tenant leases, spawns a replacement outside the scaling
    gates, and leaves interactive replicas untouched."""
    sup = _mksup(tmp_path, preempt_fraction=0.5,
                 preempt_interval_s=10.0, max_replicas=8,
                 cooldown_s=0.0, heartbeat_timeout=100.0)
    sup.advice = {"wanted_replicas": 4, "reason": "t", "inputs": {}}
    sup.step(now=0.0)
    sup.step(now=1.0)
    names = sorted(sup.replicas())
    assert len(names) == 4
    for n in names:
        sup.ledger.heartbeat(n, 0, now=1.5)
    sup.step(now=2.0)                     # all UP; no holders yet
    assert all(s != signal.SIGKILL for _, s in sup.signals)
    # two of four replicas hold campaign leases
    sup.ledger.lease_owners = \
        lambda tenant=None: {names[0]: 1, names[1]: 3}
    sup.step(now=3.0)
    # fraction 0.5 of 2 holders -> exactly 1 kill, most-loaded first
    assert (names[1], signal.SIGKILL) in sup.signals
    assert names[1] not in sup.replicas()
    assert len(sup.replicas()) == 4       # replacement spawned
    ev = [e for e in _events(tmp_path)
          if e["kind"] == "campaign-preempt"]
    assert len(ev) == 1
    assert ev[0]["replica"] == names[1]
    assert ev[0]["leases"] == 3
    assert ev[0]["tenant"] == "campaign"
    assert ev[0]["replacement"] in sup.replicas()
    # the replacement rode the ordinary spawn path, labelled
    spawn_whys = [e.get("why", "") for e in _events(tmp_path)
                  if e["kind"] == "supervisor-spawn"]
    assert any("campaign lane" in w for w in spawn_whys)
    # interval gate: the next step is inside preempt_interval_s
    sup.step(now=5.0)
    assert len([e for e in _events(tmp_path)
                if e["kind"] == "campaign-preempt"]) == 1
    # past the interval: at least one preempted while any holds one
    sup.ledger.lease_owners = lambda tenant=None: {names[0]: 1}
    sup.step(now=14.0)
    ev = [e for e in _events(tmp_path)
          if e["kind"] == "campaign-preempt"]
    assert len(ev) == 2 and ev[1]["replica"] == names[0]


def test_preempt_disabled_and_floored(tmp_path):
    """fraction 0.0 never preempts even with holders; a tiny
    fraction still preempts at least one (the floor keeps the path
    exercised, never special)."""
    sup = _mksup(tmp_path, cooldown_s=0.0)     # fraction defaults 0
    sup.advice = {"wanted_replicas": 2, "reason": "t", "inputs": {}}
    sup.step(now=0.0)
    sup.step(now=1.0)
    names = sorted(sup.replicas())
    for n in names:
        sup.ledger.heartbeat(n, 0, now=1.5)
    sup.ledger.lease_owners = \
        lambda tenant=None: {n: 1 for n in names}
    sup.step(now=2.0)
    assert all(s != signal.SIGKILL for _, s in sup.signals)
    assert not [e for e in _events(tmp_path)
                if e["kind"] == "campaign-preempt"]
    # fraction 0.1 of 2 holders rounds to 0 -> floored to 1 kill
    sup.cfg.preempt_fraction = 0.1
    sup.step(now=3.0)
    killed = [n for n, s in sup.signals if s == signal.SIGKILL]
    assert len(killed) == 1 and killed[0] in names


# ----------------------------------------------------------------------
# taxonomy + lint check 16
# ----------------------------------------------------------------------

def test_supervisor_taxonomy_subset_relations():
    from presto_tpu.obs import taxonomy
    assert taxonomy.SUPERVISOR_SPANS <= taxonomy.SERVE_SPANS
    assert taxonomy.SUPERVISOR_METRICS <= taxonomy.METRICS


def test_obs_lint_check16_clean_and_detects_drift(monkeypatch):
    from presto_tpu.lint import obscoverage
    from presto_tpu.obs import taxonomy
    assert obscoverage.lint() == []
    monkeypatch.setattr(
        taxonomy, "SUPERVISOR_METRICS",
        frozenset(taxonomy.SUPERVISOR_METRICS
                  | {"supervisor_ghost_total"}))
    problems = obscoverage.lint()
    assert any("supervisor_ghost_total" in p for p in problems)
