"""Chaos harness: deterministic fault injection (repro.runtime.faults)
driven end-to-end through the resolution/serving stack.

The contract under test: **every fault scenario ends bit-identical to a
clean library run** — worker SIGKILL mid-chunk, corrupt/truncated store
records, daemon SIGKILL mid-stream, dropped/delayed client sockets, and
straggling workers all recover (respawn + replay, quarantine +
re-resolve, failover to library mode from the committed prefix,
speculative duplicate dispatch) without changing a single bit of the
result, and every recovery is visible in the counters
(``rescache.census()``, daemon ``stats``) instead of silent.

Also covers the supporting machinery: the fault plan itself
(occurrence windows, filters, the cross-process firing registry), the
client timeout/backoff envelope, stale-socket / pidfile spawn guards,
the journal's restart replay, and the ``runtime.fault_tolerance``
policies (StepGuard, StragglerPolicy, SpeculationPolicy).
"""

import contextlib
import json
import os
import queue as _queue
import subprocess
import sys
import tempfile
import threading
import time

import pytest

from repro.core import rescache as rc
from repro.core.simulator import (acp, acp_cache, simulate_dataflow_many)
from repro.runtime import faults

from _serve_client import pipeline


@pytest.fixture()
def store(tmp_path, monkeypatch):
    d = str(tmp_path / "store")
    rc.clear()
    rc.configure(enabled=True, directory=d)
    monkeypatch.setattr(rc, "CHUNK_ITERS", 512)
    monkeypatch.setenv("REPRO_CHUNK_ITERS", "512")
    yield d
    rc.clear()
    rc.configure(enabled=False)


@pytest.fixture(autouse=True)
def disarm_faults(monkeypatch):
    """Every test starts and ends with no plan armed and a clean env."""
    monkeypatch.delenv(faults.ENV, raising=False)
    faults.reset()
    yield
    faults.reset()


@contextlib.contextmanager
def daemon(**kw):
    from repro.serve.daemon import ResolutionDaemon
    sdir = tempfile.mkdtemp(prefix="serve-")
    kw.setdefault("workers", 2)
    d = ResolutionDaemon(address=os.path.join(sdir, "d.sock"), **kw)
    d.start()
    try:
        yield d
    finally:
        d.stop()


def _key(v):
    return (v.cycles, v.cache_hits, v.cache_misses,
            v.stage_stall_cycles)


def _ref(n=5000, mems=None, depths=(8,)):
    """The clean library baseline: no rescache, streaming engine."""
    mems = mems or {"ACPC": acp_cache()}
    return simulate_dataflow_many(pipeline(n), dict(mems), n,
                                  fifo_depths=depths,
                                  use_rescache=False)


def _arm_env(monkeypatch, tmp_path, specs, name="plan"):
    """Arm a plan through the environment (reaches spawned workers and
    daemons) with a log file as the cross-process firing registry."""
    log = str(tmp_path / f"{name}.log")
    plan = {"faults": specs, "log": log}
    monkeypatch.setenv(faults.ENV, json.dumps(plan))
    faults.reset()  # re-read the env in this process too
    return log


# ---------------------------------------------------------------------------
# The fault plan itself
# ---------------------------------------------------------------------------

def test_fault_spec_matching_and_windows():
    p = faults.FaultPlan([
        {"kind": "worker_kill", "at": 2, "count": 2, "chunk": 7},
    ])
    # chunk filter: non-matching events are not even counted
    assert p.check("worker_kill", chunk=3) is None
    # occurrence window [2, 3] of *matching* events
    assert p.check("worker_kill", chunk=7) is None     # occurrence 1
    assert p.check("worker_kill", chunk=7) is not None  # 2
    assert p.check("worker_kill", chunk=7) is not None  # 3
    assert p.check("worker_kill", chunk=7) is None      # 4: window over
    assert p.injected == {"worker_kill": 2}
    with pytest.raises(ValueError):
        faults.FaultSpec("no_such_kind")


def test_fault_plan_json_roundtrip_and_env(monkeypatch, tmp_path):
    p = faults.FaultPlan([{"kind": "straggler", "delay_s": 1.5,
                           "target": 3}], seed=9, log="/tmp/x.log")
    q = faults.FaultPlan.from_json(p.to_json())
    assert q.seed == 9 and q.log == "/tmp/x.log"
    assert q.faults[0].kind == "straggler"
    assert q.faults[0].delay_s == 1.5 and q.faults[0].target == 3
    # env can hold a path to the JSON as well as inline JSON
    f = tmp_path / "plan.json"
    f.write_text(p.to_json())
    monkeypatch.setenv(faults.ENV, str(f))
    faults.reset()
    assert faults.active()
    assert faults.plan().faults[0].kind == "straggler"


def test_fault_log_is_cross_process_firing_registry(tmp_path):
    """A spec fires at most ``count`` times across *all* processes of
    the plan: a respawned worker re-armed with the same env plan must
    not re-kill itself forever (that would eat the retry budget)."""
    log = str(tmp_path / "fire.log")
    raw = json.dumps({"faults": [{"kind": "worker_kill", "chunk": 2}],
                      "log": log})
    first = faults.FaultPlan.from_json(raw)
    assert first.check("worker_kill", chunk=2) is not None  # fires+logs
    respawn = faults.FaultPlan.from_json(raw)  # fresh process simulated
    assert respawn.check("worker_kill", chunk=2) is None
    assert faults.log_counts(log) == {"worker_kill": 1}


def test_corrupt_and_truncate_are_deterministic(tmp_path):
    a, b = tmp_path / "a.bin", tmp_path / "b.bin"
    payload = bytes(range(256)) * 64
    a.write_bytes(payload)
    b.write_bytes(payload)
    faults.corrupt_file(str(a), seed=7)
    faults.corrupt_file(str(b), seed=7)
    assert a.read_bytes() == b.read_bytes() != payload
    faults.truncate_file(str(a))
    assert a.stat().st_size == len(payload) // 2


# ---------------------------------------------------------------------------
# Store integrity: checksums, quarantine, crash-safe writes
# ---------------------------------------------------------------------------

def _store_files(d):
    # chunk records only: per-chunk cache-effect records
    # (``<key>.eNNNNN.npz``) commit alongside them and would skew the
    # committed-prefix counts these drills assert on
    return sorted(f for f in os.listdir(d) if rc._CHUNK_RE.match(f))


def _one_record(store):
    """Resolve once through the store and return a record's path."""
    n = 1500
    simulate_dataflow_many(pipeline(n), {"ACPC": acp_cache()}, n,
                           fifo_depths=(8,))
    files = _store_files(store)
    assert files
    return os.path.join(store, files[0])


def test_checksum_detects_bitflips_and_quarantines(store):
    path = _one_record(store)
    key, cidx = os.path.basename(path).split(".")[0], 0
    assert rc.get_chunk(key, cidx, refresh=True) is not None
    faults.corrupt_file(path, seed=3)
    rc.clear()  # drop the memory tier so the disk record is re-read
    rc.configure(enabled=True, directory=store)
    assert rc.get_chunk(key, cidx, refresh=True) is None
    assert rc.stats()["quarantined"] == 1
    assert not os.path.exists(path)  # moved aside, never served again
    cen = rc.census()
    assert cen["quarantined"] == 1 and cen["quarantine_files"] == 1


def test_truncated_record_quarantined(store):
    path = _one_record(store)
    key = os.path.basename(path).split(".")[0]
    faults.truncate_file(path)
    rc.clear()
    rc.configure(enabled=True, directory=store)
    assert rc.get_chunk(key, 0, refresh=True) is None
    assert rc.stats()["quarantined"] == 1
    assert rc.chunk_len(key, 0) is None  # header path quarantines too


@pytest.mark.parametrize("kind", ["corrupt_chunk", "truncate_chunk"])
def test_chaos_store_damage_end_to_end(store, monkeypatch, tmp_path,
                                       kind):
    """A record damaged at write time is detected on read, quarantined,
    re-resolved, and the rerun is bit-identical to the clean baseline —
    with exactly one committed record per chunk at the end."""
    n = 2500  # 5 chunks
    ref = _ref(n)
    _arm_env(monkeypatch, tmp_path, [{"kind": kind, "chunk": 2}])
    first = simulate_dataflow_many(pipeline(n), {"ACPC": acp_cache()},
                                   n, fifo_depths=(8,))
    for k in ref:  # the writer's own run folded live ops: still clean
        assert _key(first[k]) == _key(ref[k]), k
    assert faults.stats().get(kind) == 1
    monkeypatch.delenv(faults.ENV)
    faults.reset()
    rc.clear()  # drop memory tier: force the damaged disk read
    rc.configure(enabled=True, directory=store)
    again = simulate_dataflow_many(pipeline(n), {"ACPC": acp_cache()},
                                   n, fifo_depths=(8,))
    for k in ref:
        assert _key(again[k]) == _key(ref[k]), k
    assert rc.stats()["quarantined"] >= 1
    # exactly-once: the re-resolve healed the store — 5 clean records,
    # and one more pass serves fully warm with zero cold chunks
    assert len(_store_files(store)) == 5
    rc.clear()
    rc.configure(enabled=True, directory=store)
    warm = simulate_dataflow_many(pipeline(n), {"ACPC": acp_cache()},
                                  n, fifo_depths=(8,))
    for k in ref:
        assert _key(warm[k]) == _key(ref[k]), k
    assert rc.stats()["cold_chunks"] == 0
    assert rc.stats()["quarantined"] == 0


# ---------------------------------------------------------------------------
# Chaos matrix: the serving stack under injected faults
# ---------------------------------------------------------------------------

def test_chaos_worker_sigkill_mid_chunk(store, monkeypatch, tmp_path):
    """A pool worker SIGKILLed mid-chunk: the daemon respawns the slot,
    replays its in-flight chunks, and the served result is
    bit-identical; the kill is visible in worker_restarts and the fault
    log (the killed process cannot report itself)."""
    n = 5000
    ref = _ref(n)
    log = _arm_env(monkeypatch, tmp_path,
                   [{"kind": "worker_kill", "chunk": 3}])
    from repro.serve.client import simulate_dataflow_served
    with daemon() as d:
        got = simulate_dataflow_served(pipeline(n),
                                       {"ACPC": acp_cache()}, n,
                                       fifo_depths=(8,),
                                       address=d.address)
        st = d.stats()
    for k in ref:
        assert _key(got[k]) == _key(ref[k]), k
    assert faults.log_counts(log) == {"worker_kill": 1}
    assert st["failures"]["worker_restarts"] >= 1
    assert st["failures"]["chunk_retries"] >= 1
    assert st["jobs_completed"] == 1


def test_torn_reply_is_dropped_not_awaited(monkeypatch):
    """A worker killed while it writes a reply (an OOM kill mid-send)
    leaves a torn message in its own result pipe only: the scheduler's
    read gives end of file and drops the connection instead of blocking
    for ever, and a whole reply on another worker's pipe still comes
    through."""
    import multiprocessing
    import signal

    import cloudpickle

    from repro.core.chunkgraph import recv_replies, rescache_cfg, \
        start_worker
    C = 1 << 17
    ctx = multiprocessing.get_context("spawn")
    cfg = rescache_cfg()
    procs, task_qs, conns = map(list, zip(
        *(start_worker(ctx, w, C, cfg) for w in range(2))))
    payload = cloudpickle.dumps({"stages": pipeline(2 * C),
                                 "mems": {"U": acp()}, "seed": 0,
                                 "n_iters": 2 * C, "keys": {"U": None}})
    try:
        for w in range(2):
            task_qs[w].put(("job", 0, payload))
            task_qs[w].put(("task", 0, w, w * C, (w + 1) * C))
            task_qs[w].put(("state", 0, w, {}))
            task_qs[w].put(("draws", 0, w, {"U": {"base": 0}}))
        for w in range(2):  # effect and replay of each worker's chunk
            for kind in ("effect", "replay"):
                assert conns[w].poll(120)
                assert conns[w].recv()[0] == kind
        # the done reply carries the 393 KB inline latency matrix: it
        # overfills the pipe, so worker 0 is mid-send once bytes show
        assert conns[0].poll(120)
        os.kill(procs[0].pid, signal.SIGKILL)
        procs[0].join(timeout=60)
        got = []
        t0 = time.monotonic()
        while (conns[0] is not None or not got) \
                and time.monotonic() - t0 < 120:
            got += recv_replies(conns, 1.0)
        assert conns[0] is None  # torn pipe dropped
        assert [(m[0], m[1], m[3]) for m in got] == [("done", 1, 1)]
        assert got[0][5]["U"]["ops"].shape == (C, 3)
        assert got[0][5]["U"]["ops"].nbytes > 4 * (1 << 16)
    finally:
        for w in range(2):
            task_qs[w].put(("stop",))
            task_qs[w].cancel_join_thread()
            procs[w].join(timeout=30)
            if procs[w].is_alive():
                procs[w].terminate()
        for c in conns:
            if c is not None:
                c.close()


def _start_worker_and_vanish(q):
    """A scheduler that starts one worker, reports its pid and dies
    without stopping it, once the worker is serving (it has answered a
    task of a job it never got with an error)."""
    import multiprocessing

    from repro.core.chunkgraph import rescache_cfg, start_worker
    proc, task_q, conn = start_worker(multiprocessing.get_context("spawn"),
                                      0, 1 << 10, rescache_cfg())
    task_q.put(("task", 0, 0, 0, 1))
    assert conn.recv()[0] == "error"
    q.put(proc.pid)
    q.close()
    q.join_thread()
    os._exit(0)


def test_orphaned_worker_exits():
    """A worker whose scheduler was killed (the daemon's SIGKILL drill,
    an OOM kill) exits instead of waiting for tasks for ever."""
    import multiprocessing
    ctx = multiprocessing.get_context("spawn")
    q = ctx.Queue()
    helper = ctx.Process(target=_start_worker_and_vanish, args=(q,))
    helper.start()
    pid = q.get(timeout=120)
    helper.join(timeout=60)

    def gone() -> bool:
        try:
            with open(f"/proc/{pid}/stat") as f:
                return f.read().rsplit(")", 1)[1].split()[0] == "Z"
        except FileNotFoundError:
            return True

    t0 = time.monotonic()
    while not gone() and time.monotonic() - t0 < 120:
        time.sleep(0.2)
    alive = not gone()
    if alive:
        os.kill(pid, 9)
    assert not alive, "the orphaned worker kept running"


def test_chaos_straggler_speculative_dispatch(store, monkeypatch,
                                              tmp_path):
    """A worker straggling in the heavy phase earns a speculative
    duplicate dispatch; the first commit wins, the loser is discarded,
    and the result is bit-identical.  The firing registry keeps the
    duplicate worker from re-injecting the same straggle."""
    n = 5000  # 10 chunks; straggle the last so the test stays fast
    ref = _ref(n)
    _arm_env(monkeypatch, tmp_path,
             [{"kind": "straggler", "chunk": 9, "delay_s": 8.0}])
    from repro.serve.client import simulate_dataflow_served
    with daemon(speculate_after_s=0.5) as d:
        got = simulate_dataflow_served(pipeline(n),
                                       {"ACPC": acp_cache()}, n,
                                       fifo_depths=(8,),
                                       address=d.address)
        st = d.stats()
    for k in ref:
        assert _key(got[k]) == _key(ref[k]), k
    assert st["speculation"]["issued"] >= 1
    assert st["speculation"]["wins"] >= 1
    assert st["jobs_completed"] == 1


def test_chaos_chunkgraph_straggler_speculation(store, monkeypatch,
                                                tmp_path):
    """The same bounded-staleness speculation in the chunk-graph
    executor: the master re-dispatches the straggling phase-C chunk to
    an idle peer and the sharded result stays bit-identical."""
    n = 5000
    rc.configure(enabled=False)  # pure-compute path, no store writes
    ref = _ref(n)
    _arm_env(monkeypatch, tmp_path,
             [{"kind": "straggler", "chunk": 9, "delay_s": 6.0}])
    monkeypatch.setenv("REPRO_SPECULATE_AFTER_S", "0.5")
    got = simulate_dataflow_many(pipeline(n), {"ACPC": acp_cache()}, n,
                                 fifo_depths=(8,), use_rescache=False,
                                 workers=2)
    for k in ref:
        assert _key(got[k]) == _key(ref[k]), k
    assert rc.stats()["speculated"] >= 1


def test_chaos_socket_drop_fails_over_to_library(store):
    """The daemon link dropped mid-stream: the client raises
    ServeUnavailable, ``simulate_dataflow_many`` falls back to library
    mode, resumes from the committed store prefix, and the result is
    bit-identical; the failover is counted, never silent."""
    n = 5000
    ref = _ref(n)
    faults.install(faults.FaultPlan([{"kind": "drop_socket", "at": 4}]))
    with daemon() as d:
        got = simulate_dataflow_many(pipeline(n), {"ACPC": acp_cache()},
                                     n, fifo_depths=(8,),
                                     server=d.address)
    for k in ref:
        assert _key(got[k]) == _key(ref[k]), k
    assert faults.stats() == {"drop_socket": 1}
    assert rc.stats()["serve_failovers"] == 1
    assert rc.census()["serve_failovers"] == 1


def test_chaos_socket_delay_is_absorbed(store):
    """A delayed stream is not a failure: the run just waits it out."""
    n = 1500
    ref = _ref(n)
    faults.install(faults.FaultPlan(
        [{"kind": "delay_socket", "at": 2, "delay_s": 0.4}]))
    with daemon() as d:
        got = simulate_dataflow_many(pipeline(n), {"ACPC": acp_cache()},
                                     n, fifo_depths=(8,),
                                     server=d.address)
    for k in ref:
        assert _key(got[k]) == _key(ref[k]), k
    assert faults.stats() == {"delay_socket": 1}
    assert rc.stats()["serve_failovers"] == 0


def _spawn_daemon_proc(sock, store, extra_env=None):
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               PYTHONPATH=os.path.join(repo, "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", "daemon",
         "--socket", sock, "--workers", "2", "--store-dir", store,
         "--speculate-after", "0"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    from repro.serve.client import ping
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and not ping(sock):
        if proc.poll() is not None:
            break
        time.sleep(0.2)
    return proc


def test_chaos_daemon_sigkill_and_journal_restart(store, monkeypatch,
                                                  tmp_path):
    """The centerpiece scenario: the daemon SIGKILLs itself after
    committing chunk 4 mid-stream.  (a) The client fails over to
    library mode and finishes bit-identically from the committed
    prefix.  (b) A *restarted* daemon replays its journal, re-attaches
    the half-finished job as an orphan, finishes the remainder into the
    store with no client attached, and reports monotone counters
    (restarts, resumed jobs) — after which a cold client is served the
    whole artifact with zero cold chunks."""
    n = 5000  # 10 chunks
    ref = _ref(n)
    log = str(tmp_path / "dk.log")
    plan = json.dumps({"faults": [{"kind": "daemon_kill", "chunk": 4}],
                       "log": log})
    sdir = tempfile.mkdtemp(prefix="serve-")
    sock = os.path.join(sdir, "d.sock")
    proc = _spawn_daemon_proc(sock, store,
                              extra_env={faults.ENV: plan})
    from repro.serve.client import (ServeUnavailable, get_stats, ping,
                                    shutdown, simulate_dataflow_served)
    try:
        assert ping(sock), "daemon never came up"
        # (a) serve-only attempt dies mid-stream at the kill point
        with pytest.raises(ServeUnavailable):
            simulate_dataflow_served(pipeline(n), {"ACPC": acp_cache()},
                                     n, fifo_depths=(8,), address=sock)
        assert rc.stats()["serve_failovers"] == 1
        assert faults.log_counts(log) == {"daemon_kill": 1}
        committed = len(_store_files(store))
        assert 1 <= committed < 10  # a prefix, not the whole job
        # library fallback path is what simulate_dataflow_many does:
        got = simulate_dataflow_many(pipeline(n), {"ACPC": acp_cache()},
                                     n, fifo_depths=(8,), server=sock)
        for k in ref:
            assert _key(got[k]) == _key(ref[k]), k

        # (b) journal re-attach: reap the killed daemon first (its
        # zombie pid would trip the restarted daemon's pidfile guard),
        # wipe the fallback's local completions so the restarted daemon
        # has a remainder to finish, then restart with no fault plan
        proc.wait(timeout=30)
        for f in _store_files(store)[committed:]:
            os.unlink(os.path.join(store, f))
        rc.clear()
        rc.configure(enabled=True, directory=store)
        proc2 = _spawn_daemon_proc(sock, store)
        try:
            assert ping(sock), "restarted daemon never came up"
            deadline = time.monotonic() + 120
            while time.monotonic() < deadline \
                    and len(_store_files(store)) < 10:
                time.sleep(0.5)
            assert len(_store_files(store)) == 10, \
                "restarted daemon did not finish the journaled job"
            st = get_stats(sock)
            assert st["journal"]["enabled"]
            assert st["journal"]["restarts"] >= 1
            assert st["journal"]["resumed_jobs"] >= 1
            shutdown(sock)
        finally:
            proc2.terminate()
            proc2.wait(timeout=10)
        rc.clear()
        rc.configure(enabled=True, directory=store)
        warm = simulate_dataflow_many(pipeline(n),
                                      {"ACPC": acp_cache()}, n,
                                      fifo_depths=(8,))
        for k in ref:
            assert _key(warm[k]) == _key(ref[k]), k
        assert rc.stats()["cold_chunks"] == 0
    finally:
        proc.terminate()
        proc.wait(timeout=10)


# ---------------------------------------------------------------------------
# Client resilience: timeouts, backoff, spawn guards
# ---------------------------------------------------------------------------

def test_serve_timeouts_env_and_configure(monkeypatch):
    from repro.serve import client
    monkeypatch.setenv("REPRO_SERVE_CONNECT_TIMEOUT_S", "3.5")
    monkeypatch.setenv("REPRO_SERVE_MAX_WAIT_S", "7")
    monkeypatch.setenv("REPRO_SERVE_DEADLINE_S", "42")
    t = client.ServeTimeouts.from_env()
    assert t.connect_timeout_s == 3.5
    assert t.max_wait_s == 7.0 and t.deadline_s == 42.0
    try:
        client.configure_timeouts(max_wait_s=1.25)
        assert client._cfg(None).max_wait_s == 1.25
        explicit = client.ServeTimeouts(max_wait_s=9.0)
        assert client._cfg(explicit).max_wait_s == 9.0  # arg wins
    finally:
        client.configure_timeouts(None)


def test_backoff_is_deterministic_and_capped():
    from repro.serve import client
    cfg = client.ServeTimeouts(backoff_base_s=0.05, backoff_cap_s=0.4)
    a = [client._backoff(cfg, i) for i in range(12)]
    b = [client._backoff(cfg, i) for i in range(12)]
    assert a == b  # same pid, same attempt -> same jitter
    assert all(d <= 0.4 * 2.0 for d in a)  # cap (+jitter<=cap)
    assert a[0] < a[5] or a[5] == pytest.approx(0.4, abs=0.4)


def test_connect_honors_cumulative_deadline(tmp_path):
    from repro.serve import client
    cfg = client.ServeTimeouts(max_wait_s=1.0, backoff_base_s=0.02,
                               backoff_cap_s=0.1)
    t0 = time.monotonic()
    with pytest.raises(client.ServeUnavailable):
        client._connect(str(tmp_path / "nobody.sock"), cfg,
                        time.monotonic() + cfg.max_wait_s)
    assert time.monotonic() - t0 < 5.0  # bounded, not 600s


def test_options_serve_block_configures_client(store):
    """CompileOptions.serve plumbs timeouts into the client and
    defaults server= for Compiled.simulate."""
    import jax.numpy as jnp
    from repro.dataflow import ServeOptions, compile as dfc
    from repro.serve import client

    def f(x):
        return jnp.cumsum(x * 2.0)

    c = dfc(f, jnp.arange(64, dtype=jnp.float32),
            serve=ServeOptions(max_wait_s=0.5, backoff_cap_s=0.1))
    try:
        rep = c.simulate(n_iters=256)  # no daemon: falls back locally
        assert rep is not None
        assert client._cfg(None).max_wait_s == 0.5
    finally:
        client.configure_timeouts(None)


def test_stale_socket_cleared_and_spawn_race(store, monkeypatch):
    """A dead socket file is unlinked under the spawn lock, and two
    racing ensure_daemon calls yield exactly one daemon."""
    import socket as _socket
    from repro.serve import client
    sdir = tempfile.mkdtemp(prefix="serve-")
    sock = os.path.join(sdir, "stale.sock")
    s = _socket.socket(_socket.AF_UNIX)
    s.bind(sock)
    s.close()  # bound but never listening: the crashed-daemon husk
    assert os.path.exists(sock) and not client.ping(sock)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    monkeypatch.setenv("PYTHONPATH", os.path.join(repo, "src")
                       + os.pathsep + os.environ.get("PYTHONPATH", ""))
    results, errs = [], []

    def race():
        try:
            results.append(client.ensure_daemon(sock, workers=1))
        except Exception as e:  # noqa: BLE001 — surfaced below
            errs.append(e)

    ts = [threading.Thread(target=race) for _ in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join(timeout=90)
    try:
        assert not errs, errs
        assert results == [sock, sock]
        st = client.get_stats(sock)
        assert st["workers"] == 1
    finally:
        client.shutdown(sock)


def test_pidfile_guard_rejects_second_daemon(store):
    from repro.serve.daemon import ResolutionDaemon
    sdir = tempfile.mkdtemp(prefix="serve-")
    sock = os.path.join(sdir, "d.sock")
    d1 = ResolutionDaemon(address=sock, workers=1)
    d1.start()
    try:
        d2 = ResolutionDaemon(address=sock, workers=1)
        with pytest.raises(RuntimeError, match="already"):
            d2.start()
    finally:
        d1.stop()
    assert not os.path.exists(sock + ".pid")  # clean stop removes it
    # and after a clean stop the address is reusable
    d3 = ResolutionDaemon(address=sock, workers=1)
    d3.start()
    d3.stop()


# ---------------------------------------------------------------------------
# runtime.fault_tolerance policies
# ---------------------------------------------------------------------------

def test_step_guard_retries_from_checkpoint():
    from repro.runtime.fault_tolerance import GuardConfig, StepGuard
    calls = {"restores": 0}

    def restore():
        calls["restores"] += 1
        return {"w": 0.0}, 0

    guard = StepGuard(lambda s, b: (s, {"loss": 1.0}),
                      GuardConfig(max_retries=3, restore_fn=restore,
                                  fail_at=lambda step: step == 2))
    for step in range(4):
        state, m = guard.run({"w": 0.0}, {}, step)
        assert m["loss"] == 1.0
    assert guard.failures == 1 and guard.restores == 1
    assert calls["restores"] == 1


def test_step_guard_budget_exhausted():
    from repro.runtime.fault_tolerance import (GuardConfig, StepFailure,
                                               StepGuard)

    def always_fail(s, b):
        raise StepFailure("boom")

    guard = StepGuard(always_fail, GuardConfig(max_retries=2))
    with pytest.raises(StepFailure):
        guard.run({}, {}, 0)
    assert guard.failures == 3  # initial + 2 retries


def test_straggler_policy_bounded_staleness():
    from repro.runtime.fault_tolerance import StragglerPolicy

    class Source:
        _SENTINEL = object()

        def __init__(self):
            self._q = _queue.Queue()

        def __next__(self):
            return self._q.get()

    src = Source()
    pol = StragglerPolicy(deadline_s=0.05, max_consecutive_reuse=2)
    src._q.put({"x": 1})
    assert pol.next_batch(src) == {"x": 1}
    # producer stalls: reuse the last batch, bounded
    assert pol.next_batch(src) == {"x": 1}
    assert pol.next_batch(src) == {"x": 1}
    assert pol.reused == 2
    # past the bound it must block for real
    src._q.put({"x": 2})
    assert pol.next_batch(src) == {"x": 2}


def test_speculation_policy_overdue_logic():
    from repro.runtime.fault_tolerance import SpeculationPolicy
    pol = SpeculationPolicy(min_wait_s=2.0, latency_factor=4.0)
    assert not pol.overdue(1e9)  # no samples: no baseline, never fire
    for w in (0.1, 0.2, 0.3):
        pol.observe(w)
    assert pol.median_wall() == 0.2
    assert not pol.overdue(1.9)   # floored at min_wait_s
    assert pol.overdue(2.1)
    pol2 = SpeculationPolicy(min_wait_s=0.1, latency_factor=4.0)
    for w in (1.0, 1.0, 1.0):
        pol2.observe(w)
    assert not pol2.overdue(3.9)  # 4 x median governs
    assert pol2.overdue(4.1)
    snap = pol2.snapshot()
    assert snap["median_wall_s"] == 1.0 and snap["issued"] == 0


# ---------------------------------------------------------------------------
# Observability plumbing
# ---------------------------------------------------------------------------

def test_census_carries_resilience_counters(store):
    cen = rc.census()
    for key in ("quarantined", "quarantine_files", "serve_failovers",
                "speculated", "faults_injected", "worker_retries"):
        assert key in cen, key
    faults.install(faults.FaultPlan([{"kind": "delay_socket"}]))
    faults.plan().check("delay_socket")
    assert rc.census()["faults_injected"] == {"delay_socket": 1}


def test_sweep_rows_carry_resilience_record(store):
    from repro.dataflow.schedule import sweep_schedule

    class _Sched:
        channel_bytes = 4

        def sim_stages(self, traces=None, **kw):
            return pipeline(2000)

    res = sweep_schedule(_Sched(), n_iters=2000, mems={"ACP": acp},
                         fifo_depths=(8,))
    for row in res.rows:
        assert row["resilience"] == {"worker_retries": 0,
                                     "quarantined": 0,
                                     "serve_failovers": 0}


def test_daemon_stats_report_faults_and_journal(store):
    with daemon(journal=True) as d:
        st = d.stats()
    assert st["journal"]["enabled"] is True
    assert st["journal"]["restarts"] == 0
    assert "faults_injected" in st
    assert st["speculation"] is not None  # default policy armed
    with daemon(journal=False, speculate_after_s=0) as d:
        st = d.stats()
    assert st["journal"]["enabled"] is False
    assert st["speculation"] is None
