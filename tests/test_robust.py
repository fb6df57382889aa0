"""Fault-tolerant serving runtime: deadlines, backpressure, worker
pool, circuit breaker, chaos schedules.

The serving robustness contract under test: **every submitted ticket
terminates** — with a result or a typed error — under load shedding,
deadline expiry, plan poisoning, artifact corruption, worker stalls and
clock skew; a tripped model keeps serving *correct* outputs through the
interpretive oracle engine until its re-lower probe recovers.
"""
import threading
import time

import numpy as np
import pytest

import repro.api as api
import repro.runtime.chaos as chaos
from repro.api import DeadlineExceeded, FlushError, Overloaded, WorkerLost
from repro.api.compiled import CompiledModel
from repro.core import program_cache_clear, program_cache_configure, \
    program_cache_info
from repro.runtime.fault import FaultMonitor
from repro.runtime.serving import CircuitBreaker, \
    LatencyHistogram, ServerPool, Ticket

from test_execplan import random_graph, _inputs


@pytest.fixture(autouse=True)
def _isolated_cache():
    saved = program_cache_info()
    program_cache_clear()
    program_cache_configure(max_entries=64, max_bytes=None, disk_dir=None)
    yield
    program_cache_clear()
    program_cache_configure(max_entries=saved["max_entries"],
                            max_bytes=saved["max_bytes"],
                            disk_dir=saved["disk_dir"])


def _session(**kw):
    kw.setdefault("max_batch", 4)
    sess = api.Session(**kw)
    sess.add(random_graph(0), name="m0", precision="int8")
    return sess


def _feed(sess, name="m0", seed=0):
    return _inputs(sess[name].graph, 1, seed)[0]


def _check_output(sess, name, out, feed):
    want = sess[name](feed, engine="interp")
    for k in want:
        err = float(np.max(np.abs(out[k] - want[k])))
        assert err <= sess[name].semantics.plan_parity_tol(k), \
            f"{name}/{k}: served output diverged from oracle by {err}"


# --------------------------------------------------------------------------
# fault monitor fixes (heartbeat registry)
# --------------------------------------------------------------------------


@pytest.mark.fast
def test_fault_monitor_dead_hosts_at_time_zero():
    """now=0.0 must be honoured, not silently replaced by wall time."""
    mon = FaultMonitor(n_hosts=2, timeout_s=1.0)
    assert mon.dead_hosts(now=0.0) == []


@pytest.mark.fast
def test_fault_monitor_beat_tolerates_unknown_host():
    mon = FaultMonitor(n_hosts=1, timeout_s=1.0)
    mon.beat(7, step=3, step_time_s=0.5)     # auto-registers
    assert 7 in mon.beats and mon.step_times[7] == [0.5]
    mon.retire(7)
    assert 7 not in mon.beats and 7 not in mon.step_times
    mon.retire(7)                            # idempotent


# --------------------------------------------------------------------------
# primitives: histogram + breaker
# --------------------------------------------------------------------------


@pytest.mark.fast
def test_latency_histogram_percentiles():
    h = LatencyHistogram()
    for ms in range(1, 101):
        h.record(float(ms))
    snap = h.snapshot()
    assert snap["count"] == 100
    assert 45 <= snap["p50_ms"] <= 56       # log-bucket edge tolerance
    assert 90 <= snap["p99_ms"] <= 110
    assert snap["max_ms"] == 100.0
    assert abs(snap["mean_ms"] - 50.5) < 1e-6


@pytest.mark.fast
def test_circuit_breaker_state_machine():
    br = CircuitBreaker(threshold=3, cooldown_s=1.0)
    assert br.allow_plan()
    assert not br.record_failure(now=0.0)
    assert not br.record_failure(now=0.0)
    br.record_success()                      # success resets the streak
    assert not br.record_failure(now=0.0)
    assert not br.record_failure(now=0.0)
    assert br.record_failure(now=0.0)        # third consecutive: trips
    assert br.state == "open" and not br.allow_plan()
    assert not br.try_probe(now=0.5)         # cooldown not elapsed
    assert br.try_probe(now=1.5)             # claims the probe
    assert br.state == "half_open"
    assert not br.try_probe(now=1.5)         # only one winner
    br.probe_failed(now=1.5)
    assert br.state == "open"
    assert br.try_probe(now=3.0)
    br.probe_succeeded()
    assert br.state == "closed" and br.allow_plan()
    assert br.snapshot()["trips"] == 1 and br.snapshot()["recoveries"] == 1


# --------------------------------------------------------------------------
# admission control + deadlines (sync mode)
# --------------------------------------------------------------------------


@pytest.mark.fast
def test_bounded_queue_sheds_with_retry_hint():
    sess = _session(max_queue=5)
    x = _feed(sess)
    for _ in range(5):
        sess.submit("m0", x)
    with pytest.raises(Overloaded) as ei:
        sess.submit("m0", x)
    assert ei.value.model == "m0"
    assert ei.value.queue_depth == 5
    assert ei.value.retry_after_ms >= 1.0
    assert sess.flush() == 5
    assert sess.stats()["models"]["m0"]["shed"] == 1
    sess.submit("m0", x)                     # capacity freed
    sess.flush()


@pytest.mark.fast
def test_deadline_expiry_ordering():
    """Expired tickets fail with DeadlineExceeded *without executing*;
    live tickets in the same queue still run."""
    sess = _session()
    x = _feed(sess)
    t_dead = sess.submit("m0", x, deadline_ms=0.0)    # expires instantly
    assert t_dead.done and isinstance(t_dead.error, DeadlineExceeded)
    before = sess.stats()["models"]["m0"]["requests"]

    with chaos.inject() as c:
        t_soon = sess.submit("m0", x, deadline_ms=1.0)
        t_late = sess.submit("m0", x, deadline_ms=10_000.0)
        t_none = sess.submit("m0", x)
        c.skew_clock(0.5)            # half a second passes "instantly"
        sess.flush("m0")
    with pytest.raises(DeadlineExceeded) as ei:
        t_soon.result()
    assert ei.value.late_ms > 0
    _check_output(sess, "m0", t_late.result(), x)
    _check_output(sess, "m0", t_none.result(), x)
    st = sess.stats()["models"]["m0"]
    assert st["deadline_misses"] == 2
    # the expired tickets consumed zero execution
    assert st["requests"] == before + 2


@pytest.mark.fast
def test_per_model_flush_does_not_drain_other_models():
    sess = _session()
    sess.add(random_graph(1), name="m1")
    t0 = sess.submit("m0", _feed(sess, "m0"))
    t1 = sess.submit("m1", _feed(sess, "m1"))
    assert sess.flush("m0") == 1
    assert t0.done and not t1.done
    t1.result()                              # resolves via its own model
    assert sess.queue_depth == 0


@pytest.mark.fast
def test_flush_aggregates_errors_and_drains_every_model():
    sess = _session()
    sess.add(random_graph(1), name="m1")
    sess.add(random_graph(2), name="m2")
    bad = np.zeros((3, 3, 1), dtype=np.float32)       # wrong shape
    t0 = sess.submit("m0", bad)
    t1 = sess.submit("m1", _feed(sess, "m1"))
    t2 = sess.submit("m2", bad)
    with pytest.raises(FlushError) as ei:
        sess.flush()
    assert set(ei.value.errors) == {"m0", "m2"}       # both recorded
    assert t1.done and t1.error is None               # m1 still executed
    assert isinstance(t0.error, ValueError)
    assert isinstance(t2.error, ValueError)
    assert sess.queue_depth == 0
    # client errors never count against the breaker
    assert sess.stats()["models"]["m0"]["breaker"]["state"] == "closed"
    assert sess.stats()["models"]["m0"]["plan_failures"] == 0


# --------------------------------------------------------------------------
# circuit breaker: trip -> degraded oracle serving -> recovery
# --------------------------------------------------------------------------


@pytest.mark.fast
@pytest.mark.chaos
def test_transient_fault_retried_once():
    sess = _session(retry_backoff_ms=1.0)
    x = _feed(sess)
    with chaos.inject() as c:
        c.poison_plan("m0", times=1)         # first attempt only
        t = sess.submit("m0", x)
        _check_output(sess, "m0", t.result(), x)
    st = sess.stats()["models"]["m0"]
    assert st["retries"] == 1 and st["plan_failures"] == 0
    assert st["breaker"]["state"] == "closed"


@pytest.mark.fast
@pytest.mark.chaos
def test_breaker_trips_then_serves_oracle_then_recovers():
    sess = _session(breaker_threshold=2, breaker_cooldown_s=0.1,
                    retry_backoff_ms=1.0)
    x = _feed(sess)
    with chaos.inject() as c:
        for _ in range(2):                   # 2 batches, both retries fail
            c.poison_plan("m0", times=2)
            t = sess.submit("m0", x)
            with pytest.raises(chaos.ChaosError):
                t.result()
        st = sess.stats()["models"]["m0"]
        assert st["breaker"]["state"] == "open"
        assert st["breaker_trips"] == 1 and st["plan_failures"] == 2

        # keep the plan poisoned through the first *background* probe:
        # it must fail, stay open and re-arm itself (recovery no longer
        # piggybacks on request batches)
        c.poison_plan("m0", times=1)

        # open: requests degrade to the interpretive oracle — correct
        t = sess.submit("m0", x)
        _check_output(sess, "m0", t.result(), x)
        assert sess.stats()["models"]["m0"]["degraded_requests"] >= 1

        deadline = time.monotonic() + 5.0
        while time.monotonic() < deadline:
            st = sess.stats()["models"]["m0"]
            if st["failed_recoveries"] >= 1:
                break
            time.sleep(0.02)
        assert st["failed_recoveries"] == 1
        assert st["breaker"]["state"] == "open"

    # chaos gone: the re-armed probe heals the breaker with no request
    # traffic at all (an idle model recovers too)
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        st = sess.stats()["models"]["m0"]
        if st["breaker"]["state"] == "closed":
            break
        time.sleep(0.02)
    assert st["breaker"]["state"] == "closed" and st["recoveries"] == 1
    t = sess.submit("m0", x)
    _check_output(sess, "m0", t.result(), x)
    st = sess.stats()["models"]["m0"]
    assert st["latency"]["count"] > 0 and st["latency"]["p99_ms"] > 0


@pytest.mark.fast
@pytest.mark.chaos
def test_corrupt_artifact_takes_recompile_path():
    """A corrupted disk-tier artifact is rejected and recompiled, not
    served."""
    import tempfile
    with tempfile.TemporaryDirectory() as d:
        program_cache_configure(disk_dir=d)
        m = api.compile(random_graph(5), precision="int8")
        program_cache_clear()                # memory tier gone; disk stays
        with chaos.inject() as c:
            c.corrupt_artifacts(times=1)
            m2 = api.compile(random_graph(5), precision="int8")
        assert c.injected["artifact_faults"] == 1
        info = program_cache_info()
        assert info["disk_rejects"] >= 1
        x = _inputs(m.graph, 1, 0)[0]
        got, want = m2(x), m(x, engine="interp")
        for k in want:
            err = float(np.max(np.abs(got[k] - want[k])))
            assert err <= m.semantics.plan_parity_tol(k)


# --------------------------------------------------------------------------
# worker pool
# --------------------------------------------------------------------------


@pytest.mark.chaos
def test_pool_serves_and_close_fails_leftovers():
    sess = _session(workers=2, linger_ms=1.0)
    x = _feed(sess)
    ts = [sess.submit("m0", x) for _ in range(8)]
    for t in ts:
        _check_output(sess, "m0", t.result(timeout=30), x)
    st = sess.stats()
    assert st["pool"]["dispatched_requests"] >= 8
    assert all(h["alive"] for h in st["workers"].values())
    sess.close()
    with pytest.raises(Exception):
        sess.submit("m0", x)


@pytest.mark.chaos
def test_pool_recycles_stalled_worker_zero_ticket_loss():
    """A worker that stops heartbeating mid-batch is detected, its
    in-flight batch re-dispatched, the worker recycled — and every
    ticket still terminates with a correct result."""
    sess = _session(workers=2, heartbeat_timeout_s=0.15, linger_ms=1.0)
    x = _feed(sess)
    with chaos.inject() as c:
        c.stall_worker(0, seconds=1.2)
        c.stall_worker(1, seconds=1.2)
        ts = [sess.submit("m0", _feed(sess, seed=i)) for i in range(10)]
        outs = [t.result(timeout=30) for t in ts]
    assert all(o is not None for o in outs)
    st = sess.stats()["pool"]
    assert st["recycled_workers"] >= 1
    assert st["redispatched_batches"] >= 1
    assert len(sess.stats()["workers"]) > 2  # replacements spawned
    sess.close()


@pytest.mark.chaos
def test_pool_deadline_auto_flush_is_latency_bounded():
    """With no other traffic, a deadline submission dispatches on its
    own — well before the deadline — rather than waiting for a full
    batch or a cooperative flush."""
    sess = _session(workers=1, linger_ms=500.0)   # linger alone too slow
    x = _feed(sess)
    t0 = time.monotonic()
    t = sess.submit("m0", x, deadline_ms=100.0)
    _check_output(sess, "m0", t.result(timeout=10), x)
    assert (time.monotonic() - t0) < 0.4          # NOT the 500 ms linger
    sess.close()


# --------------------------------------------------------------------------
# property: every ticket terminates under randomized fault schedules
# --------------------------------------------------------------------------


@pytest.mark.chaos
@pytest.mark.parametrize("seed", [0, 1, 2])
def test_every_ticket_terminates_under_random_faults(seed):
    rng = np.random.default_rng(seed)
    sess = _session(workers=2, max_queue=32, heartbeat_timeout_s=0.15,
                    linger_ms=1.0, breaker_threshold=2,
                    breaker_cooldown_s=0.1, retry_backoff_ms=1.0)
    sess.add(random_graph(1), name="m1")
    names = ["m0", "m1"]
    tickets, shed = [], 0
    with chaos.inject() as c:
        for step in range(60):
            r = rng.random()
            if r < 0.08:
                c.poison_plan(str(rng.choice(names)),
                              times=int(rng.integers(1, 3)))
            elif r < 0.12:
                c.stall_worker(int(rng.integers(0, 6)),
                               seconds=float(rng.uniform(0.2, 0.6)))
            elif r < 0.15:
                c.skew_clock(float(rng.uniform(0.0, 0.05)))
            name = str(rng.choice(names))
            deadline = float(rng.uniform(5, 500)) \
                if rng.random() < 0.4 else None
            try:
                tickets.append(sess.submit(
                    name, _feed(sess, name, seed=step),
                    deadline_ms=deadline))
            except Overloaded:
                shed += 1
            if rng.random() < 0.2:
                time.sleep(0.01)
        # ZERO ticket loss: every accepted ticket terminates, each with
        # a value or a *typed* serving error
        for t in tickets:
            try:
                t.result(timeout=30)
            except (DeadlineExceeded, WorkerLost, chaos.ChaosError):
                pass
        assert all(t.done for t in tickets)
    assert len(tickets) + shed == 60
    sess.close()
    # post-mortem: the accounting adds up
    st = sess.stats()
    served = sum(m["latency"]["count"] for m in st["models"].values()
                 if "latency" in m)
    failed = sum(1 for t in tickets if t.error is not None)
    assert served + failed >= len(tickets)   # backups may double-serve


@pytest.mark.chaos
def test_sync_session_random_faults_single_thread():
    """The same termination property in synchronous (workers=0) mode."""
    rng = np.random.default_rng(7)
    sess = _session(max_queue=16, breaker_threshold=2,
                    breaker_cooldown_s=0.05, retry_backoff_ms=1.0)
    x = _feed(sess)
    tickets = []
    with chaos.inject() as c:
        for step in range(40):
            if rng.random() < 0.15:
                c.poison_plan("m0", times=int(rng.integers(1, 3)))
            if rng.random() < 0.1:
                c.skew_clock(float(rng.uniform(0, 0.02)))
            try:
                tickets.append(sess.submit(
                    "m0", x, deadline_ms=float(rng.uniform(5, 200))
                    if rng.random() < 0.5 else None))
            except Overloaded:
                pass
            if rng.random() < 0.3:
                try:
                    sess.flush("m0")
                except FlushError:
                    pass
        try:
            sess.flush()
        except FlushError:
            pass
    assert all(t.done for t in tickets)
    assert sess.queue_depth == 0


@pytest.mark.chaos
def test_concurrent_submitters_one_pool():
    """Many client threads hammering one pooled session: every ticket
    terminates, results are correct."""
    sess = _session(workers=2, max_queue=128, linger_ms=1.0)
    x = _feed(sess)
    want = sess["m0"](x, engine="interp")
    errs, done = [], []
    lock = threading.Lock()

    def client(n):
        for _ in range(n):
            try:
                t = sess.submit("m0", x)
                out = t.result(timeout=30)
                for k in want:
                    assert float(np.max(np.abs(out[k] - want[k]))) <= \
                        sess["m0"].semantics.plan_parity_tol(k)
                with lock:
                    done.append(1)
            except Overloaded:
                pass
            except Exception as e:       # pragma: no cover - diagnostics
                with lock:
                    errs.append(e)

    threads = [threading.Thread(target=client, args=(10,))
               for _ in range(6)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs, errs
    assert len(done) > 0
    sess.close()


# --------------------------------------------------------------------------
# fault monitor: retire tombstones
# --------------------------------------------------------------------------


@pytest.mark.fast
def test_fault_monitor_retire_tombstone_drops_late_beats():
    """A recycled worker's id is tombstoned: its straggler beats are
    dropped (no zombie resurrection in the registry), and only an
    explicit register() — the replacement spawn — re-admits the id."""
    mon = FaultMonitor(n_hosts=0, timeout_s=1.0)
    mon.register(3)
    mon.beat(3, step=0, step_time_s=0.1)
    mon.retire(3)
    mon.beat(3, step=1, step_time_s=0.1)     # late beat from the corpse
    assert 3 not in mon.beats                # swallowed, not re-admitted
    assert mon.dead_hosts(now=99.0) == []    # and never reported dead
    mon.register(3)                          # replacement reuses the id
    mon.beat(3, step=2, step_time_s=0.1)
    assert 3 in mon.beats


# --------------------------------------------------------------------------
# EDF dispatch + priority classes (queue unit tests, workers=0)
# --------------------------------------------------------------------------


@pytest.mark.fast
def test_edf_pop_order_within_model():
    """Within one model's queue, batches pop earliest-deadline-first;
    deadline-less work rides behind every dated entry."""
    pool = ServerPool(lambda name, entries: None, workers=0,
                      max_batch=4, linger_ms=0.0)
    try:
        now = chaos.now()
        for label, dl in (("A", now + 200.0), ("B", now + 50.0),
                          ("C", None), ("D", now + 100.0)):
            pool.submit("m0", label, Ticket(None, "m0", dl))
        with pool._cv:
            claim, _ = pool._claim_locked(chaos.now())
        assert claim is not None
        name, entries = claim
        assert name == "m0"
        assert [feed for feed, _ in entries] == ["B", "D", "A", "C"]
    finally:
        pool.close()


@pytest.mark.fast
def test_priority_class_dispatch_across_models():
    """Across models, the higher priority class dispatches first even
    when the lower-priority queue has waited longer."""
    pool = ServerPool(lambda name, entries: None, workers=0,
                      max_batch=4, linger_ms=0.0)
    try:
        pool.set_priority("hi", 1)
        for i in range(2):
            pool.submit("lo", f"lo{i}", Ticket(None, "lo"))
        for i in range(2):
            pool.submit("hi", f"hi{i}", Ticket(None, "hi"))
        time.sleep(0.002)                    # step past the zero linger
        with pool._cv:
            first, _ = pool._claim_locked(chaos.now())
            second, _ = pool._claim_locked(chaos.now())
        assert first is not None and first[0] == "hi"
        assert second is not None and second[0] == "lo"
    finally:
        pool.close()


@pytest.mark.fast
def test_pool_saturation_sheds_low_priority_first():
    """Pool-wide saturation evicts a lower-priority model's least
    urgent entry to admit high-priority work; a low-priority arrival
    with no victim below it is shed."""
    pool = ServerPool(lambda name, entries: None, workers=0,
                      max_batch=4, max_queue=8, max_queue_total=3,
                      linger_ms=1e6)
    try:
        pool.set_priority("hi", 1)
        lo = [Ticket(None, "lo") for _ in range(3)]
        for i, t in enumerate(lo):
            pool.submit("lo", f"lo{i}", t)
        t_hi = Ticket(None, "hi")
        pool.submit("hi", "hi0", t_hi)       # evicts one lo entry
        assert pool.counters["priority_evictions"] == 1
        assert sum(1 for t in lo
                   if isinstance(t.error, Overloaded)) == 1
        assert not t_hi.done                 # admitted, not shed
        with pytest.raises(Overloaded):      # no victim below priority 0
            pool.submit("lo", "lox", Ticket(None, "lo"))
        assert pool.queue_depth("hi") == 1
    finally:
        pool.close()


# --------------------------------------------------------------------------
# process pool: mmap'd worker processes, crash recovery
# --------------------------------------------------------------------------


def _proc_session(n=2):
    sess = api.Session(workers=("process", n), max_batch=4,
                       heartbeat_timeout_s=2.0)
    sess.add(random_graph(0), name="m0", precision="int8")
    return sess


@pytest.mark.chaos
def test_process_pool_parity():
    """workers=("process", n) serves through real child processes (own
    pids, mmap'd artifacts) with the same outputs as the in-process
    interpretive oracle."""
    import os
    sess = _proc_session()
    try:
        feeds = [_feed(sess, seed=i) for i in range(8)]
        ts = [sess.submit("m0", f) for f in feeds]
        for t, f in zip(ts, feeds):
            _check_output(sess, "m0", t.result(timeout=30), f)
        health = sess._pool.worker_health()
        pids = {h["pid"] for h in health.values() if h.get("pid")}
        assert pids and os.getpid() not in pids
        assert sess.stats()["pool"]["dispatched_requests"] >= 8
    finally:
        sess.close()


@pytest.mark.chaos
@pytest.mark.parametrize("mode", ["kill", "segv", "oom"])
def test_process_pool_crash_zero_ticket_loss(mode):
    """SIGKILL / SIGSEGV / simulated-OOM abort of a worker process with
    its batch in flight: the batch re-dispatches to survivors, every
    ticket resolves correctly, and the replacement worker spawns off
    the request path."""
    sess = _proc_session()
    try:
        feeds = [_feed(sess, seed=i) for i in range(10)]
        with chaos.inject() as c:
            c.kill_worker(-1, mode=mode)
            ts = [sess.submit("m0", f) for f in feeds]
            # zero ticket loss: every ticket resolves with parity,
            # served by the surviving worker — no respawn on this path
            for t, f in zip(ts, feeds):
                _check_output(sess, "m0", t.result(timeout=30), f)
            assert c.stats()["kills"] == 1
        assert sess.stats()["models"]["m0"]["crash_redispatches"] >= 1
        # ... and the supervisor respawns the replacement afterwards
        deadline = time.monotonic() + 20
        while time.monotonic() < deadline:
            st = sess.stats()["pool"]
            ready = [h for h in sess._pool.worker_health().values()
                     if h.get("ready")]
            if st.get("recycled_workers", 0) >= 1 and len(ready) >= 2:
                break
            time.sleep(0.1)
        assert sess.stats()["pool"]["recycled_workers"] >= 1
        assert len([h for h in sess._pool.worker_health().values()
                    if h.get("ready")]) >= 2
    finally:
        sess.close()


# --------------------------------------------------------------------------
# artifact v3: persisted lowered-plan constants
# --------------------------------------------------------------------------


def _tamper_zip(src, dst, member, fn):
    """Rewrite a zip, transforming one member's bytes with fn (return
    None to drop the member)."""
    import zipfile
    with zipfile.ZipFile(src) as zin, \
            zipfile.ZipFile(dst, "w", zipfile.ZIP_STORED) as zout:
        for item in zin.infolist():
            blob = zin.read(item.filename)
            if item.filename == member:
                blob = fn(blob)
                if blob is None:
                    continue
            zout.writestr(item.filename, blob)


def test_v3_artifact_serves_plan_consts(tmp_path):
    """save() persists the lowered-plan kernel constants; a loading
    worker's first plan serves them (computed == 0) with exact parity."""
    m = api.compile(random_graph(3), precision="int8")
    x = _inputs(m.graph, 1, 0)[0]
    want = m(x, engine="plan")
    p = str(tmp_path / "m.rpa")
    m.save(p)
    assert m.plan_cache_info()["consts"] > 0
    m2 = CompiledModel.load(p, mmap=True)
    got = m2(x, engine="plan")
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])
    info = m2.plan_cache_info()
    assert info["consts_computed"] == 0 and info["consts_served"] > 0
    # invalidation never trusts persisted consts again: fresh recompute
    m2.invalidate_plans()
    got = m2(x, engine="plan")
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])
    assert m2.plan_cache_info()["consts_computed"] > 0


def test_consts_free_artifact_recomputes(tmp_path):
    """Back-compat: an artifact written without plan constants (the
    pre-v3 layout) loads fine and re-derives them on first plan."""
    from repro.api import artifact as artifact_mod
    m = api.compile(random_graph(4), precision="int8")
    x = _inputs(m.graph, 1, 0)[0]
    want = m(x, engine="plan")
    p = str(tmp_path / "old.rpa")
    artifact_mod.save_model(
        p, name=m.name, graph=m.graph, cfg=m.cfg, options=m.options,
        result=m.result, weights=m.weights, precision=m.precision,
        quant_meta=m.semantics.meta()
        if hasattr(m.semantics, "meta") else None,
        qweights=m.qm.qweights, packed=m.qm.packed,
        calib_error=m.qm.calib_error)        # no plan_consts=
    m2 = CompiledModel.load(p)
    got = m2(x, engine="plan")
    for k in want:
        np.testing.assert_array_equal(got[k], want[k])
    info = m2.plan_cache_info()
    assert info["consts_computed"] > 0 and info["consts_served"] == 0


def test_corrupt_plan_const_member_rejected(tmp_path):
    """A flipped byte inside a persisted constant fails the sha256
    manifest — the artifact is rejected, never served."""
    from repro.core.serialize import ArtifactError
    m = api.compile(random_graph(3), precision="int8")
    p = str(tmp_path / "m.rpa")
    m.save(p)
    bad = str(tmp_path / "bad.rpa")
    _tamper_zip(p, bad, "arrays/pl/0000.npy",
                lambda b: b[:-1] + bytes([b[-1] ^ 0xFF]))
    with pytest.raises(ArtifactError):
        CompiledModel.load(bad)


def test_missing_plan_const_member_rejected(tmp_path):
    """A planconsts index that references a missing array member is a
    typed ArtifactError, not a KeyError deep in lowering."""
    import json
    from repro.core.serialize import ArtifactError
    m = api.compile(random_graph(3), precision="int8")
    p = str(tmp_path / "m.rpa")
    m.save(p)

    def drop_from_manifest(blob):
        meta = json.loads(blob.decode("utf-8"))
        del meta["manifest"]["arrays/pl/0000.npy"]
        return json.dumps(meta).encode("utf-8")

    bad = str(tmp_path / "bad.rpa")
    _tamper_zip(p, bad, "arrays/pl/0000.npy", lambda b: None)
    _tamper_zip(bad, str(tmp_path / "bad2.rpa"), "meta.json",
                drop_from_manifest)
    with pytest.raises(ArtifactError, match="missing"):
        CompiledModel.load(str(tmp_path / "bad2.rpa"))


# --------------------------------------------------------------------------
# frame integrity: CRC32 on the pipe protocol
# --------------------------------------------------------------------------


@pytest.mark.fast
def test_frame_crc_roundtrip_and_blob_flip():
    """Every frame carries a CRC32; a flipped payload byte surfaces as
    a typed FrameCorrupt that still carries the parsed header (so the
    fault is attributable to one request), while a header flip — the
    framing itself untrustworthy — stays a ProtocolError."""
    from repro.runtime import procpool
    from repro.runtime.procpool import ProtocolError, unpack_frame
    from repro.runtime.serving import FrameCorrupt

    arrs = {"y": np.arange(12, dtype=np.float32).reshape(3, 4)}
    buf = bytes(procpool.pack_frame({"type": "res", "req": 7}, arrs))
    header, out = unpack_frame(buf)
    assert header["req"] == 7
    np.testing.assert_array_equal(out["y"], arrs["y"])

    flipped = bytearray(buf)
    flipped[-3] ^= 0x40                    # inside the blob region
    with pytest.raises(FrameCorrupt) as ei:
        unpack_frame(bytes(flipped))
    assert ei.value.header["req"] == 7     # fault is attributable

    hdr_flip = bytearray(buf)
    hdr_flip[procpool._HDR_OFF] ^= 0x40    # breaks the JSON open-brace
    with pytest.raises(ProtocolError, match="unreadable header"):
        unpack_frame(bytes(hdr_flip))


@pytest.mark.fast
def test_chaos_frame_flip_targets_payload_frames():
    """The chaos bit-flip injector corrupts exactly one payload-bearing
    frame; headers-only frames (heartbeats) pass through with the arm
    unconsumed, so the fault always lands where a batch can feel it."""
    from repro.runtime import procpool
    from repro.runtime.serving import FrameCorrupt

    hb = bytes(procpool.pack_frame({"type": "hb", "w": 0, "seq": 1}))
    res = bytes(procpool.pack_frame({"type": "res", "req": 3},
                                    {"y": np.ones(4, np.float32)}))
    with chaos.inject() as c:
        c.corrupt_frames(1)
        assert c.maybe_flip_frame(hb) == hb          # passthrough
        assert c.stats()["frame_flips"] == 0         # arm unconsumed
        bad = c.maybe_flip_frame(res)
        assert bad != res and c.stats()["frame_flips"] == 1
        assert c.maybe_flip_frame(res) == res        # one-shot
    with pytest.raises(FrameCorrupt):
        procpool.unpack_frame(bad)
    procpool.unpack_frame(res)                       # original intact


def test_process_worker_never_imports_jax():
    """Pool children replay numpy plans only.  An accelerator belongs to
    one process, so a spawned child must come up without JAX."""
    import multiprocessing as mp

    from repro.runtime import procpool

    ctx = mp.get_context("spawn")
    parent, child = ctx.Pipe(duplex=True)
    proc = ctx.Process(target=procpool._worker_main,
                       args=(child, 0, {}, 0.05, 0), daemon=True)
    proc.start()
    child.close()
    try:
        assert parent.poll(60), "worker never reported ready"
        header, _ = procpool.unpack_frame(parent.recv_bytes())
        assert header["type"] == "ready"
        assert header["jax"] is False
        parent.send_bytes(procpool.pack_frame({"type": "close"}))
    finally:
        proc.join(10)
        if proc.is_alive():
            proc.kill()


@pytest.mark.chaos
def test_process_pool_frame_corruption_zero_ticket_loss():
    """A bit-flipped reply frame fails only its own batch — the batch
    re-dispatches and every ticket still resolves with parity, with no
    worker recycled (the stream is not poisoned: length-prefixed
    framing survives payload corruption)."""
    sess = _proc_session()
    try:
        feeds = [_feed(sess, seed=i) for i in range(8)]
        with chaos.inject() as c:
            c.corrupt_frames(1)
            ts = [sess.submit("m0", f) for f in feeds]
            for t, f in zip(ts, feeds):
                _check_output(sess, "m0", t.result(timeout=30), f)
            assert c.stats()["frame_flips"] == 1
        assert sess.stats()["models"]["m0"]["frame_corrupt"] >= 1
        assert sess.stats()["pool"]["recycled_workers"] == 0
    finally:
        sess.close()


# --------------------------------------------------------------------------
# client-side retry budgets and request cancellation
# --------------------------------------------------------------------------


@pytest.mark.fast
def test_submit_retries_absorb_shed_until_queue_drains():
    """submit(retries=N) retries an Overloaded shed with jittered
    exponential backoff seeded from the shed hint, succeeding once a
    drain frees the bounded queue."""
    sess = api.Session(max_queue=2)
    sess.add(random_graph(0), name="m0", precision="int8")
    try:
        x = _feed(sess)
        for _ in range(2):
            sess.submit("m0", x)                   # fill the queue
        with pytest.raises(Overloaded):
            sess.submit("m0", x)                   # retries=0: shed

        th = threading.Thread(
            target=lambda: (time.sleep(0.01), sess.flush("m0")))
        th.start()
        t = sess.submit("m0", x, retries=12, retry_cap_ms=100.0)
        th.join()
        _check_output(sess, "m0", t.result(timeout=30), x)
        assert sess.stats()["models"]["m0"]["submit_retries"] >= 1
    finally:
        sess.close()


@pytest.mark.fast
def test_submit_retries_respect_deadline():
    """The retry loop never sleeps past the request deadline: a queue
    that stays full sheds with Overloaded before the deadline burns."""
    sess = api.Session(max_queue=1)
    sess.add(random_graph(0), name="m0", precision="int8")
    try:
        x = _feed(sess)
        sess.submit("m0", x)
        t0 = time.monotonic()
        with pytest.raises(Overloaded):
            sess.submit("m0", x, deadline_ms=80.0, retries=50,
                        retry_cap_ms=1000.0)
        assert (time.monotonic() - t0) < 1.0
    finally:
        sess.close()


@pytest.mark.fast
def test_cancel_queued_drops_from_edf_queue():
    """Cancelling a ticket still queued settles it Cancelled and frees
    its EDF heap slot immediately; the pool keeps serving."""
    sess = _session(workers=1, linger_ms=500.0)   # linger: stays queued
    try:
        x = _feed(sess)
        t = sess.submit("m0", x)
        assert sess._pool.queue_depth("m0") == 1
        assert t.cancel() is True
        assert sess._pool.queue_depth("m0") == 0  # heap slot freed
        with pytest.raises(api.Cancelled):
            t.result(timeout=5)
        assert t.cancel() is False                # already settled
        t2 = sess.submit("m0", x)
        _check_output(sess, "m0", t2.result(timeout=30), x)
        assert sess.stats()["models"]["m0"]["cancelled"] == 1
    finally:
        sess.close()


@pytest.mark.chaos
def test_cancel_in_flight_first_settlement_wins():
    """Cancelling a ticket already executing races the real result:
    exactly one settlement wins (Cancelled or the value, never both,
    never neither) and the pool is undisturbed either way."""
    sess = _session(workers=1, linger_ms=1.0, heartbeat_timeout_s=30.0)
    try:
        x = _feed(sess)
        with chaos.inject() as c:
            c.stall_worker(0, seconds=0.4)
            t = sess.submit("m0", x)
            time.sleep(0.1)                       # claimed, stalled
            won = t.cancel()
        if won:
            with pytest.raises(api.Cancelled):
                t.result(timeout=30)
            assert sess.stats()["models"]["m0"]["cancelled"] == 1
        else:
            _check_output(sess, "m0", t.result(timeout=30), x)
        t2 = sess.submit("m0", x)
        _check_output(sess, "m0", t2.result(timeout=30), x)
    finally:
        sess.close()
