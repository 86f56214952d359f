"""Replica fleet serving (repro.serving.fleet): router, stickiness,
failover, elasticity.

Acceptance invariants (ISSUE 10):

* placement — prefix-affine prompts land where the cached pages live;
  cold/disjoint traffic spreads least-loaded; a saturated replica spills
  admission to a peer BEFORE its own shed path fires;
* sessions — sticky to their replica (turn N+1 reuses the retained tail
  there), and a replica crash migrates them via journal replay with the
  next turn's greedy output bit-identical to an uninterrupted server;
* elasticity — drain() quiesces + migrates + closes without losing a
  session; add_replica() takes traffic;
* the unit-level migration precondition — a journal replayed into a FRESH
  ``LLMServer`` instance (same config, different object) continues greedy
  bit-identically in all three cache modes.
"""
import threading
import time

import pytest
from _host_devices import run_on_host_devices

from repro.configs.registry import ARCHS
from repro.serving.faults import OverloadError
from repro.serving.fleet import FleetServer
from repro.serving.server import (EngineConfig, LLMServer, OverloadPolicy,
                                  SamplingParams)

T1 = "user: hello there assistant:"
DELTA = " user: and what else? assistant:"


def _cfg(arch="qwen2.5-3b"):
    return ARCHS[arch].reduced(dtype="float32", param_dtype="float32",
                               vocab_size=512)


def _ecfg(mode="paged", page_size=8):
    return EngineConfig(cache_mode=mode, page_size=page_size)


def _fleet(cfg=None, **kw):
    kw.setdefault("num_replicas", 2)
    kw.setdefault("num_slots", 2)
    kw.setdefault("capacity", 128)
    kw.setdefault("engine_cfg", _ecfg())
    kw.setdefault("seed", 3)
    kw.setdefault("digest_ttl_s", 0.0)      # always-fresh digests: routing
    return FleetServer(cfg or _cfg(), **kw)  # decisions are deterministic


def _reference_turns(cfg, params, ecfg):
    """Uninterrupted single-server two-turn session: the bit-identity
    oracle every fleet path must match (same shared weights, greedy)."""
    srv = LLMServer(cfg, num_slots=2, capacity=128, engine_cfg=ecfg, seed=3,
                    params=params)
    sp = SamplingParams(max_new_tokens=8)
    sess = srv.open_session()
    out1 = sess.submit(T1, sp).result()
    out2 = sess.submit(sess.text + DELTA, sp).result()
    srv.close()
    return out1, out2


def _replica_of(fleet, handle):
    """Which replica served this handle (handles are replica-level)."""
    for r in fleet.replicas:
        if handle._server is r.server:
            return r.idx
    raise AssertionError("handle's server is not a fleet replica")


def _slow_steps(server, delay_s=0.05):
    """Wedge a replica's engine loop so parked work lingers long enough to
    hold its slot + admission queue (the reduced model otherwise decodes
    64 tokens in well under 100ms)."""
    real = server._step_impl

    def slow():
        time.sleep(delay_s)
        return real()

    server._step_impl = slow


def _wait_saturated(replicas, timeout_s=10.0):
    """Block until every given replica shows a non-empty admission queue.
    The park/queue submits above land via pump commands, so there is a
    window where the queued request has not yet been observed; probing the
    fleet before the queues are visibly full would race the precondition."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if all(len(r.server.engine._queue) >= 1 for r in replicas):
            return
        time.sleep(0.01)
    raise AssertionError("replicas never reached admission-queue saturation")


def _crash_replica(fleet, idx, timeout_s=10.0):
    """Kill one replica's pump the way the chaos tests do: its next loop
    iteration raises, the pump dies, outstanding work fails typed."""
    srv = fleet.replicas[idx].server

    def boom():
        raise RuntimeError(f"injected crash on replica {idx}")

    srv._step_impl = boom
    deadline = time.monotonic() + timeout_s
    while srv.pumping and time.monotonic() < deadline:
        time.sleep(0.01)
    assert not srv.pumping, "replica pump did not die"


# ---- routing ---------------------------------------------------------------
def test_fleet_roundtrip_and_gauges():
    """N sessionless submits through the fleet all complete; the fleet
    gauges account for every placement."""
    with _fleet() as fleet:
        sp = SamplingParams(max_new_tokens=6)
        hs = [fleet.submit(f"request number {i} topic {i % 3} ", sp)
              for i in range(6)]
        outs = [h.result() for h in hs]
        assert all(isinstance(o, str) for o in outs)
        st = fleet.stats()
        assert st["fleet_replicas"] == 2
        assert st["routed_requests"] == 6
        assert st["migrated_sessions"] == 0
        assert st["queued_requests"] == 0 and st["live_requests"] == 0
        # aggregate counters really sum across replicas
        assert st["decode_tokens"] == sum(
            p["decode_tokens"] for p in st["per_replica"])


def test_least_loaded_tiebreak_spreads_cold_traffic():
    """Disjoint prompts on an idle fleet: the routed-count tie-break must
    alternate replicas instead of piling everything on replica 0."""
    with _fleet() as fleet:
        sp = SamplingParams(max_new_tokens=4)
        for i in range(4):
            # drain each before the next so load scores are 0 (a pure tie);
            # prompts differ from the FIRST token so affinity never matches
            fleet.submit(f"{i} unrelated prompt {i} " * 3, sp).result()
        counts = [r.routed for r in fleet.replicas]
        assert counts == [2, 2], counts


def test_prefix_affinity_lands_on_the_warm_replica():
    """After one prompt warms a replica's radix, prompts sharing its first
    block must land on THAT replica (digest hit), and actually radix-hit
    there."""
    shared = "incident report for region seven: "      # >= page_size tokens
    with _fleet() as fleet:
        sp = SamplingParams(max_new_tokens=4)
        warm = fleet.submit(shared + "first occurrence", sp)
        warm.result()
        warm_idx = _replica_of(fleet, warm)
        fleet.run_until_idle()                         # radix adopts pages
        hs = [fleet.submit(shared + f"follow-up {i}", sp) for i in range(3)]
        for h in hs:
            h.result()
        assert all(_replica_of(fleet, h) == warm_idx for h in hs)
        st = fleet.stats()
        assert st["affinity_hits"] >= 3
        assert st["per_replica"][warm_idx]["prefix_hit_tokens"] > 0


def test_saturated_replica_spills_to_peer_before_shedding():
    """Affinity prefers the warm replica, but when its admission queue is
    at the OverloadPolicy bound and a peer has headroom, the placement
    spills — the fleet never invokes one replica's shed path while another
    could serve."""
    shared = "the hot shared prefix everybody re-sends: "
    with _fleet(num_slots=1, overload=OverloadPolicy(max_queue_depth=1),
                engine_cfg=EngineConfig(cache_mode="paged", page_size=8,
                                        decode_chunk=2)) as fleet:
        sp = SamplingParams(max_new_tokens=4)
        warm = fleet.submit(shared + "warm", sp)
        warm.result()
        warm_idx = _replica_of(fleet, warm)
        fleet.run_until_idle()
        # saturate the warm replica: slow its loop, then park one long
        # decode in its slot with a full admission queue behind it
        # 96 tokens x chunk 2 x 0.25s/step ~= 12s: the parked decode outlives
        # the probe even when first-use compiles stall the pumps under suite
        # load (capacity=128 caps max_new_tokens, so stretch time per step)
        _slow_steps(fleet.replicas[warm_idx].server, delay_s=0.25)
        long_sp = SamplingParams(max_new_tokens=96)
        park = fleet.replicas[warm_idx].server.submit(
            shared + "park", long_sp)
        queued = fleet.replicas[warm_idx].server.submit(
            shared + "queued", long_sp)
        _wait_saturated([fleet.replicas[warm_idx]])
        # affinity says warm replica; saturation must spill to the peer
        h = fleet.submit(shared + "spilled arrival", sp)
        assert _replica_of(fleet, h) != warm_idx
        assert h.result() is not None
        st = fleet.stats()
        assert st["spilled_admissions"] >= 1
        park.cancel()
        queued.cancel()
        fleet.run_until_idle()


def test_all_replicas_saturated_raises_typed_overload():
    with _fleet(num_slots=1, overload=OverloadPolicy(max_queue_depth=1),
                engine_cfg=EngineConfig(cache_mode="paged", page_size=8,
                                        decode_chunk=2)) as fleet:
        long_sp = SamplingParams(max_new_tokens=96)
        parked = []
        for r in fleet.replicas:            # fill every slot + every queue
            _slow_steps(r.server, delay_s=0.25)
            parked.append(r.server.submit("park " * 4, long_sp))
            parked.append(r.server.submit("queue " * 4, long_sp))
        _wait_saturated(fleet.replicas)
        with pytest.raises(OverloadError):
            fleet.submit("one too many", SamplingParams(max_new_tokens=4))
        for p in parked:
            p.cancel()
        fleet.run_until_idle()


# ---- sessions --------------------------------------------------------------
def test_sessions_sticky_and_bit_identical():
    """A fleet session's turns all go to its pinned replica, reuse the
    retained tail there (turn_prefix_hits), and reproduce the uninterrupted
    single-server outputs exactly."""
    cfg = _cfg()
    with _fleet(cfg) as fleet:
        ref1, ref2 = _reference_turns(cfg, fleet.params, _ecfg())
        sp = SamplingParams(max_new_tokens=8)
        fs = fleet.open_session()
        assert fs.replica_index is None            # pinned lazily
        assert fleet.submit(T1, sp, session=fs.sid).result() == ref1
        pin = fs.replica_index
        assert pin is not None
        assert fs.submit(fs.text + DELTA, sp).result() == ref2
        assert fs.replica_index == pin
        st = fleet.stats()["per_replica"][pin]
        assert st["turn_prefix_hits"] >= 1
        fs.close()
        assert fleet.stats()["fleet_sessions"] == 0


def test_crash_migrates_sessions_bit_identically():
    """Kill a replica's pump under live sessions: the fleet detects the
    death, journal-replays every pinned session onto the healthy peer, and
    turn 2 continues greedy-bit-identically vs an uninterrupted server."""
    cfg = _cfg()
    with _fleet(cfg) as fleet:
        ref1, ref2 = _reference_turns(cfg, fleet.params, _ecfg())
        sp = SamplingParams(max_new_tokens=8)
        sessions = [fleet.open_session() for _ in range(3)]
        for s in sessions:
            assert s.submit(T1, sp).result() == ref1
        victim = sessions[0].replica_index     # same prompt => all co-pinned
        assert all(s.replica_index == victim for s in sessions)
        _crash_replica(fleet, victim)
        # next interaction (no explicit check_health call) triggers failover
        outs = [s.submit(s.text + DELTA, sp).result() for s in sessions]
        assert outs == [ref2] * 3
        st = fleet.stats()
        assert st["replicas_failed"] == 1
        assert st["migrated_sessions"] == 3
        assert st["fleet_replicas"] == 1
        assert all(s.replica_index != victim for s in sessions)


def test_crash_with_no_sessions_keeps_serving():
    """Sessionless traffic re-routes around a dead replica; the in-flight
    request on the dead pump fails typed, later submits succeed."""
    with _fleet() as fleet:
        sp = SamplingParams(max_new_tokens=4)
        fleet.submit("before the crash", sp).result()
        _crash_replica(fleet, 0)
        h = fleet.submit("after the crash", sp)
        assert _replica_of(fleet, h) == 1
        h.result()
        assert fleet.stats()["fleet_replicas"] == 1


# ---- elasticity ------------------------------------------------------------
def test_drain_migrates_and_add_replica_takes_traffic():
    cfg = _cfg()
    with _fleet(cfg) as fleet:
        ref1, ref2 = _reference_turns(cfg, fleet.params, _ecfg())
        sp = SamplingParams(max_new_tokens=8)
        fs = fleet.open_session()
        assert fs.submit(T1, sp).result() == ref1
        pin = fs.replica_index
        fleet.drain(pin)
        st = fleet.stats()
        assert st["replicas_drained"] == 1 and st["fleet_replicas"] == 1
        assert fleet.replicas[pin].removed
        assert fs.replica_index != pin                  # migrated off
        assert fs.submit(fs.text + DELTA, sp).result() == ref2
        idx = fleet.add_replica()
        assert idx == 2 and fleet.stats()["fleet_replicas"] == 2
        # cold replica wins the routed-count tie-break for fresh traffic
        h = fleet.submit("fresh arrival for the new replica", sp)
        assert _replica_of(fleet, h) == idx
        h.result()


def test_drain_last_replica_with_sessions_refuses():
    from repro.serving.faults import PumpStalledError
    with _fleet() as fleet:
        fs = fleet.open_session()
        fs.submit(T1, SamplingParams(max_new_tokens=4)).result()
        other = 1 - fs.replica_index
        fleet.drain(other)
        with pytest.raises(PumpStalledError):
            fleet.drain(fs.replica_index)


# ---- fame drivers ----------------------------------------------------------
def test_cobatch_driver_rides_the_fleet():
    """fame/fusion.CoBatchDriver over a FleetServer: pumping=True makes it
    fan out workers; concurrent chains complete with correct outputs."""
    from repro.fame.fusion import CoBatchDriver
    cfg = _cfg()
    with _fleet(cfg) as fleet:
        ref1, _ = _reference_turns(cfg, fleet.params, _ecfg())
        sp = SamplingParams(max_new_tokens=8)
        driver = CoBatchDriver(fleet)
        sessions = [fleet.open_session() for _ in range(4)]

        def turn(s):
            return driver.call(
                lambda: fleet.submit(T1, sp, session=s.sid)).request

        thunks = [lambda s=s: turn(s) for s in sessions]
        reqs = driver.run(thunks)
        assert all(r.status == "completed" for r in reqs)
        assert all(s.text.endswith(ref1) for s in sessions)


# ---- cross-instance journal portability (unit precondition) ---------------
@pytest.mark.parametrize("arch,mode", [
    ("qwen2.5-3b", "dense"),
    ("qwen2.5-3b", "paged"),
    ("recurrentgemma-9b", "paged"),        # resolves to snapshot mode
])
def test_journal_restores_into_fresh_server_instance(arch, mode):
    """The in-memory journal OBJECT of server A, replayed into a brand-new
    LLMServer B (same config, different instance — the exact fleet
    migration path), must continue the conversation greedy-bit-identically
    in every cache mode."""
    cfg = _cfg(arch)
    ecfg = EngineConfig(cache_mode=mode, page_size=8)
    sp = SamplingParams(max_new_tokens=8)
    a = LLMServer(cfg, num_slots=2, capacity=128, engine_cfg=ecfg, seed=3)
    sess = a.open_session()
    sess.submit(T1, sp).result()

    b = LLMServer(cfg, num_slots=2, capacity=128, engine_cfg=ecfg, seed=3,
                  params=a.params)
    restored = b.restore_sessions(a.journal)     # object, not a spill path
    bs = restored[sess.sid]
    assert bs.text == sess.text and bs.turns == sess.turns

    t2 = sess.text + DELTA
    ref2 = sess.submit(t2, sp).result()          # A continues uninterrupted
    assert bs.submit(t2, sp).result() == ref2, (arch, mode)
    a.close()
    b.close()


_PER_DEVICE = r'''
import jax
from repro.configs.registry import ARCHS
from repro.launch.mesh import make_host_mesh
from repro.serving.fleet import FleetServer
from repro.serving.server import EngineConfig, LLMServer, SamplingParams

cfg = ARCHS["qwen2.5-3b"].reduced(vocab_size=512)
devs = jax.devices()[:2]
ecfg = EngineConfig(cache_mode="paged", page_size=8, spec_len=2)
fleet = FleetServer(cfg, num_replicas=2, capacity=128, engine_cfg=ecfg,
                    pump=False, meshes=[make_host_mesh(d) for d in devs])
for d, r in zip(devs, fleet.replicas):
    eng = r.server.engine
    held = {x for a in jax.tree.leaves((eng.params, eng.cache))
            for x in a.devices()}
    assert held == {d}, (d, held)
prompts = [f"request {i}: tell me about tool number {i}" for i in range(4)]
sp = SamplingParams(max_new_tokens=10)
hs = [fleet.submit(p, sp) for p in prompts]
got = [h.result() for h in hs]
assert all(n > 0 for n in (p["decode_tokens"]
                           for p in fleet.stats()["per_replica"]))
one = LLMServer(cfg, capacity=128, params=fleet.params, engine_cfg=ecfg)
want = [h.result() for h in [one.submit(p, sp) for p in prompts]]
assert got == want, (got, want)
print("PER_DEVICE_OK")
'''


def test_replicas_on_their_own_devices_match_one_replica(tmp_path):
    """A replica built on a one-device mesh holds its weights and pools on
    that device (and computes there); greedy outputs equal one replica
    serving the same requests. Runs in a child with two host devices."""
    assert "PER_DEVICE_OK" in run_on_host_devices(tmp_path, _PER_DEVICE, 2)
