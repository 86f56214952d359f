"""Background pump (repro.serving.pump): the always-on serving loop.

Acceptance invariant (ISSUE 8): seeded outputs through a pumping server are
bit-identical to the cooperative ``step()`` loop across dense / paged /
snapshot cache modes — the pump changes WHO drives, never WHAT runs. Plus
lifecycle (close cancels, context manager, step() ownership), thread-safe
submission from many threads, and the typed crash/stall surface.
"""
import threading
import time

import jax
import jax.numpy as jnp
import pytest

from repro.configs.registry import ARCHS
from repro.serving.server import (EngineConfig, LLMServer, PumpConfig,
                                  PumpStalledError, SamplingParams,
                                  StepOutcome)


def _cfg(arch):
    return ARCHS[arch].reduced(dtype="float32", param_dtype="float32",
                               vocab_size=512)


MODES = [("qwen2.5-3b", "dense"), ("qwen2.5-3b", "paged"),
         ("recurrentgemma-9b", "paged")]          # paged resolves: pages/snaps

PROMPTS = ["alpha prompt for slot one",
           "a rather longer second prompt that crosses a bucket",
           "third prompt"]


@pytest.mark.parametrize("arch,mode", MODES)
def test_pump_bit_identical_to_cooperative(arch, mode):
    """Same weights, same seed, same submits: the pump thread must produce
    exactly the cooperative loop's outputs (temperature > 0 so the
    per-request RNG chains are exercised, not just argmax)."""
    cfg = _cfg(arch)
    ecfg = EngineConfig(cache_mode=mode, page_size=16)
    sp = SamplingParams(max_new_tokens=8, temperature=0.7)
    coop = LLMServer(cfg, num_slots=3, capacity=128, seed=3, engine_cfg=ecfg)
    hs = [coop.submit(p, sp) for p in PROMPTS]
    coop.run_until_idle()
    ref = [h.result() for h in hs]
    params = coop.params
    coop.close()

    with LLMServer(cfg, num_slots=3, capacity=128, seed=3, params=params,
                   engine_cfg=ecfg, pump=True) as srv:
        assert srv.pumping
        hs2 = [srv.submit(p, sp) for p in PROMPTS]
        assert [h.result() for h in hs2] == ref, (arch, mode)
        st = srv.stats()
        assert st["pump_alive"] and st["pump_steps"] > 0
        assert st["pump_stall_notices"] == 0


def test_pump_owns_the_step_loop():
    """While the pump runs, driving step() from another thread is a
    programming error (two threads would race the engine) — typed refusal,
    and run_until_idle() delegates to the pump instead."""
    with LLMServer(_cfg("qwen2.5-3b"), num_slots=2, capacity=64,
                   pump=True) as srv:
        with pytest.raises(RuntimeError, match="pump owns the step loop"):
            srv.step()
        h = srv.submit("hello", SamplingParams(max_new_tokens=4))
        srv.run_until_idle()                      # blocks on the pump
        assert h.status().value == "completed"
    # after close the server is cooperative again: step() works
    assert not srv.pumping
    assert srv.step() is StepOutcome.IDLE


def test_pump_close_cancels_outstanding():
    """close() without drain= must leave nothing stranded: outstanding
    requests reach terminal CANCELLED on the pump thread before it exits."""
    srv = LLMServer(_cfg("qwen2.5-3b"), num_slots=1, capacity=128,
                    engine_cfg=EngineConfig(decode_chunk=2), pump=True)
    hs = [srv.submit(f"long job {i} " * 4,
                     SamplingParams(max_new_tokens=64)) for i in range(3)]
    srv.close()
    assert all(h.request.finished for h in hs)
    assert any(h.status().value == "cancelled" for h in hs)
    eng = srv.engine
    assert not eng._queue and all(s.request is None for s in eng.slots)


def test_pump_close_drain_finishes_work():
    srv = LLMServer(_cfg("qwen2.5-3b"), num_slots=2, capacity=64, pump=True)
    hs = [srv.submit(p, SamplingParams(max_new_tokens=4)) for p in PROMPTS]
    srv.close(drain=True)
    assert all(h.status().value == "completed" for h in hs)


def test_pump_close_is_idempotent():
    """Double-close — sequential, with or without drain, on the pump or
    through the server — must be a no-op, never a raise or a hang."""
    srv = LLMServer(_cfg("qwen2.5-3b"), num_slots=2, capacity=64, pump=True)
    pump = srv._pump
    h = srv.submit("hello", SamplingParams(max_new_tokens=4))
    srv.close(drain=True)
    assert h.status().value == "completed"
    pump.close()                         # direct second close on the pump
    pump.close(drain=True)               # drain on an already-dead pump
    srv.close()                          # server-level close is also safe
    assert not pump.thread.is_alive()


def test_pump_close_while_handle_waits():
    """A handle blocked in result() while another thread closes the server
    must unblock with its partial CANCELLED output — close() cancels on
    the pump thread and the waiter sees a clean shutdown, not a spurious
    PumpStalledError and not a deadlock."""
    srv = LLMServer(_cfg("qwen2.5-3b"), num_slots=1, capacity=256,
                    engine_cfg=EngineConfig(decode_chunk=2), pump=True)
    h = srv.submit("a long job " * 4, SamplingParams(max_new_tokens=128))
    box = {}

    def waiter():
        try:
            box["text"] = h.result()
        except BaseException as e:       # pragma: no cover - the regression
            box["exc"] = e

    t = threading.Thread(target=waiter, daemon=True)
    t.start()
    while h.request.status == "queued":  # let it start decoding
        pass
    srv.close()
    t.join(10.0)
    assert not t.is_alive(), "waiter deadlocked across close()"
    assert "exc" not in box, box.get("exc")
    assert h.status().value == "cancelled"
    assert box["text"] == h.request.output_text


def test_pump_concurrent_close_races_are_safe():
    """Two threads racing close() (e.g. a fleet teardown and a with-block
    exit): both return, nothing raises, outstanding work is terminal."""
    srv = LLMServer(_cfg("qwen2.5-3b"), num_slots=1, capacity=128,
                    engine_cfg=EngineConfig(decode_chunk=2), pump=True)
    pump = srv._pump
    hs = [srv.submit(f"job {i} " * 4, SamplingParams(max_new_tokens=64))
          for i in range(2)]
    errs = []

    def closer():
        try:
            pump.close()
        except BaseException as e:       # pragma: no cover - the regression
            errs.append(e)

    ts = [threading.Thread(target=closer) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(10.0)
    assert not any(t.is_alive() for t in ts)
    assert not errs, errs
    assert all(h.request.finished for h in hs)
    srv.close()                          # idempotent server-level follow-up


def test_pump_threadsafe_submit_many_threads():
    """Submits racing from many client threads: every request completes,
    and each prompt's greedy output matches the single-threaded reference
    (the command queue serializes engine access, so no interleaving can
    corrupt another request's state)."""
    cfg = _cfg("qwen2.5-3b")
    sp = SamplingParams(max_new_tokens=6)
    coop = LLMServer(cfg, num_slots=4, capacity=128)
    prompts = [f"client {i} asks question {i % 3} " for i in range(12)]
    hs = [coop.submit(p, sp) for p in prompts]
    coop.run_until_idle()
    ref = {p: h.result() for p, h in zip(prompts, hs)}
    params = coop.params
    coop.close()

    with LLMServer(cfg, num_slots=4, capacity=128, params=params,
                   pump=True) as srv:
        out = {}
        lock = threading.Lock()

        def client(shard):
            for p in shard:
                r = srv.submit(p, sp).result()
                with lock:
                    out[p] = r

        threads = [threading.Thread(target=client, args=(prompts[i::4],))
                   for i in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        assert out == ref


def test_pump_crash_surfaces_typed():
    """An engine-level crash on the pump thread must not strand waiters:
    the pump dies, waits raise PumpStalledError (with the cause chained),
    and post-mortem stats()/state reads still work inline."""
    srv = LLMServer(_cfg("qwen2.5-3b"), num_slots=1, capacity=64, pump=True)
    boom = RuntimeError("injected engine crash")

    def crash():
        raise boom
    srv._step_impl = crash
    h = srv.submit("doomed", SamplingParams(max_new_tokens=4))
    with pytest.raises(PumpStalledError):
        h.result()
    assert not srv.pumping
    st = srv.stats()                               # inline post-mortem read
    assert st["pump_alive"] is False
    srv.close()


def test_pump_stall_watchdog():
    """A wedged pump (heartbeat stops — e.g. a dispatch stuck in jit)
    surfaces as a typed stall to waiters instead of a silent hang, and the
    stall is counted in stats."""
    srv = LLMServer(_cfg("qwen2.5-3b"), num_slots=1, capacity=64,
                    pump=PumpConfig(stall_timeout_s=0.2, poll_s=0.02))
    release = threading.Event()
    real = srv._step_impl

    def wedged():
        release.wait(5.0)       # hold the pump thread well past the timeout
        return real()

    srv._step_impl = wedged
    h = srv.submit("hello", SamplingParams(max_new_tokens=4))
    with pytest.raises(PumpStalledError, match="stale"):
        h.result()
    assert srv._pump.stall_notices >= 1
    release.set()               # un-wedge so shutdown is clean
    srv._step_impl = real
    pump = srv._pump
    srv.close()
    # the short stall_timeout_s also bounds close()'s join — give the
    # thread real time to leave its final engine step before teardown
    pump.thread.join(30.0)
    assert not pump.thread.is_alive()


def _compiling_first_step(srv, hold_s):
    """Make the pump's first step trace a fresh program (JAX then records
    a compile start on the pump thread) and hold it ``hold_s``, as a cold
    full-width compile would."""
    real = srv._step_impl
    first = threading.Event()

    def step():
        if not first.is_set():
            first.set()
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7.0))
            time.sleep(hold_s)
        return real()
    srv._step_impl = step


def test_pump_compiling_step_is_not_a_stall():
    """A step that compiles may outlast stall_timeout_s: waiters hold it to
    compile_timeout_s instead, and the step is counted. The server's own
    programs compile on the pump thread too and are counted alike."""
    with LLMServer(_cfg("qwen2.5-3b"), num_slots=1, capacity=64,
                   pump=PumpConfig(stall_timeout_s=0.5, poll_s=0.02)) as srv:
        _compiling_first_step(srv, 2.0)
        h = srv.submit("hello", SamplingParams(max_new_tokens=4))
        h.result()
        assert h.status().value == "completed"
        st = srv.stats()
        assert st["pump_stall_notices"] == 0
        assert st["pump_compile_steps"] >= 1
        assert st["pump_longest_compile_s"] >= 2.0
        # a prompt in a longer prefill bucket compiles a new program
        srv.submit("a prompt long enough for another prefill bucket",
                   SamplingParams(max_new_tokens=4)).result()
        assert srv.stats()["pump_compile_steps"] > st["pump_compile_steps"]


def test_pump_compile_timeout():
    """A compiling step that outlasts compile_timeout_s is a typed stall."""
    srv = LLMServer(_cfg("qwen2.5-3b"), num_slots=1, capacity=64,
                    pump=PumpConfig(stall_timeout_s=0.5, poll_s=0.02,
                                    compile_timeout_s=0.3))
    _compiling_first_step(srv, 3.0)
    h = srv.submit("hello", SamplingParams(max_new_tokens=4))
    with pytest.raises(PumpStalledError, match="compiling step"):
        h.result()
    pump = srv._pump
    srv.close()
    pump.thread.join(30.0)
    assert not pump.thread.is_alive()
