"""Chaos smoke: the fault-injection acceptance scenarios as a benchmark.

Runs the three headline failure drills from the chaos harness
(``repro.runtime.faults``) against fresh stores and checks the resilience
contract end to end:

- **worker_kill** — a pool worker SIGKILLs itself mid-chunk; the daemon
  respawns the slot and replays its in-flight chunks.  The served
  result must be bit-identical to a clean library run and the store
  must hold exactly one record per chunk.
- **corrupt_record** — a store record is damaged at publish time; the
  next run detects the bad checksum, quarantines the record,
  re-resolves the gap, and re-commits it — after which a third run
  serves fully warm with zero cold chunks.
- **daemon_restart** — the daemon SIGKILLs itself mid-stream; the
  client fails over to library mode from the committed prefix
  (bit-identically), and a *restarted* daemon replays its journal and
  finishes the orphaned job into the store with no client attached.

Every scenario's identity check and the exactly-once store accounting
are **hard failures**; results land in the ``chaos`` section of
``BENCH_sim.json`` so ``bench_trend.py`` gates resilience regressions
(the wall is tolerance-gated, identity/exactly-once fail on the
current run alone).  Run directly::

    python -m benchmarks.chaos_smoke [--out BENCH_sim.json]
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

import numpy as np

BENCH_PATH = "BENCH_sim.json"
#: Small canonical chunks so every drill spans many scheduling units.
CHUNK_ITERS = 512


def _pipeline(n: int, seed: int = 5):
    from repro.core.simulator import MemAccess, SimStage
    rng = np.random.default_rng(seed)
    return [
        SimStage("addr", ii=1, latency=2,
                 accesses=[MemAccess("i", np.arange(n) * 4)]),
        SimStage("fetch", ii=1, latency=3,
                 accesses=[MemAccess("x", rng.integers(0, 1 << 19, n) * 4),
                           MemAccess("y", np.arange(n) * 4 + (1 << 22),
                                     is_store=True)]),
        SimStage("fma", ii=4, latency=6),
    ]


def _row(v) -> tuple:
    return (v.cycles, v.cache_hits, v.cache_misses,
            v.stage_stall_cycles)


def _run(n: int, **kw) -> dict:
    from repro.core.simulator import acp_cache, simulate_dataflow_many
    out = simulate_dataflow_many(_pipeline(n), {"ACPC": acp_cache()}, n,
                                 fifo_depths=(8,), **kw)
    return {k: _row(v) for k, v in out.items()}


def _records(store: str) -> int:
    try:
        return len([f for f in os.listdir(store) if f.endswith(".npz")])
    except OSError:
        return 0


def _fresh_store(rc, work: str, name: str) -> str:
    d = os.path.join(work, name)
    rc.clear()
    rc.configure(enabled=True, directory=d)
    return d


def _spawn_daemon(sock: str, store: str, extra_env=None):
    from repro.serve.client import ping
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ,
               PYTHONPATH=os.path.join(repo, "src") + os.pathsep
               + os.environ.get("PYTHONPATH", ""),
               REPRO_CHUNK_ITERS=str(CHUNK_ITERS))
    env.update(extra_env or {})
    proc = subprocess.Popen(
        [sys.executable, "-m", "repro.launch.serve", "daemon",
         "--socket", sock, "--workers", "2", "--store-dir", store,
         "--speculate-after", "0"],
        env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL)
    deadline = time.monotonic() + 60
    while time.monotonic() < deadline and not ping(sock):
        if proc.poll() is not None:
            raise RuntimeError("chaos daemon died during start-up")
        time.sleep(0.2)
    if not ping(sock):
        proc.kill()
        raise RuntimeError("chaos daemon never came up")
    return proc


def _drill_worker_kill(rc, work: str, n: int, ref: dict) -> dict:
    """SIGKILL one pool worker mid-chunk; serve through the daemon."""
    from repro.runtime import faults
    from repro.serve.client import (get_stats, shutdown,
                                    simulate_dataflow_served)
    store = _fresh_store(rc, work, "store_wk")
    sock = os.path.join(work, "wk.sock")
    log = os.path.join(work, "wk.log")
    plan = json.dumps({"faults": [{"kind": "worker_kill", "chunk": 3}],
                       "log": log})
    proc = _spawn_daemon(sock, store, extra_env={faults.ENV: plan})
    try:
        from repro.core.simulator import acp_cache
        out = simulate_dataflow_served(
            _pipeline(n), {"ACPC": acp_cache()}, n, fifo_depths=(8,),
            address=sock)
        st = get_stats(sock)
        shutdown(sock)
        proc.wait(timeout=30)
    finally:
        if proc.poll() is None:
            proc.kill()
    got = {k: _row(v) for k, v in out.items()}
    return {"identical": got == ref,
            "worker_restarts": st["failures"]["worker_restarts"],
            "fault_fired": faults.log_counts(log).get("worker_kill", 0),
            "records": _records(store),
            "expect_records": -(-n // CHUNK_ITERS)}


def _drill_corrupt_record(rc, work: str, n: int, ref: dict) -> dict:
    """Damage one record at publish; quarantine + re-resolve heals it."""
    from repro.runtime import faults
    store = _fresh_store(rc, work, "store_cr")
    log = os.path.join(work, "cr.log")
    faults.install(faults.FaultPlan(
        [{"kind": "corrupt_chunk", "chunk": 2}], log=log))
    try:
        first = _run(n)
    finally:
        faults.install(None)
    rc.clear()  # drop the memory tier: force the damaged disk read
    rc.configure(enabled=True, directory=store)
    healed = _run(n)
    quarantined = rc.stats()["quarantined"]
    rc.clear()
    rc.configure(enabled=True, directory=store)
    warm = _run(n)
    return {"identical": first == ref and healed == ref and warm == ref,
            "quarantined": quarantined,
            "warm_cold_chunks": rc.stats()["cold_chunks"],
            "records": _records(store),
            "expect_records": -(-n // CHUNK_ITERS)}


def _drill_daemon_restart(rc, work: str, n: int, ref: dict) -> dict:
    """SIGKILL the daemon mid-stream; fail over, then journal-resume."""
    from repro.runtime import faults
    from repro.serve.client import (ServeUnavailable, get_stats,
                                    shutdown, simulate_dataflow_served)
    from repro.core.simulator import acp_cache
    store = _fresh_store(rc, work, "store_dr")
    sock = os.path.join(work, "dr.sock")
    log = os.path.join(work, "dr.log")
    plan = json.dumps({"faults": [{"kind": "daemon_kill", "chunk": 4}],
                       "log": log})
    expect = -(-n // CHUNK_ITERS)
    proc = _spawn_daemon(sock, store, extra_env={faults.ENV: plan})
    died_mid_stream = False
    try:
        try:
            simulate_dataflow_served(_pipeline(n),
                                     {"ACPC": acp_cache()}, n,
                                     fifo_depths=(8,), address=sock)
        except ServeUnavailable:
            died_mid_stream = True
        committed = _records(store)
        # failover path: the committed prefix serves, the rest resolves
        # locally — this is what simulate_dataflow_many does on its own
        got = _run(n)
        proc.wait(timeout=30)  # reap: a zombie would trip the pidfile
    finally:
        if proc.poll() is None:
            proc.kill()
    # wipe the failover's local completions so the restarted daemon has
    # a journaled remainder to finish with no client attached
    recs = sorted(f for f in os.listdir(store) if f.endswith(".npz"))
    for f in recs[committed:]:
        os.unlink(os.path.join(store, f))
    rc.clear()
    rc.configure(enabled=True, directory=store)
    proc2 = _spawn_daemon(sock, store)
    try:
        deadline = time.monotonic() + 120
        while time.monotonic() < deadline and _records(store) < expect:
            time.sleep(0.5)
        st = get_stats(sock)
        shutdown(sock)
        proc2.wait(timeout=30)
    finally:
        if proc2.poll() is None:
            proc2.kill()
    rc.clear()
    rc.configure(enabled=True, directory=store)
    warm = _run(n)
    return {"identical": got == ref and warm == ref,
            "died_mid_stream": died_mid_stream,
            "committed_prefix": committed,
            "journal_restarts": st["journal"]["restarts"],
            "resumed_jobs": st["journal"]["resumed_jobs"],
            "warm_cold_chunks": rc.stats()["cold_chunks"],
            "records": _records(store),
            "expect_records": expect}


def run_smoke(out_path: str = BENCH_PATH, n: int = 5000) -> dict:
    from repro.core import rescache as rc

    t0 = time.perf_counter()
    work = tempfile.mkdtemp(prefix="chaos-smoke-")
    old_ci = rc.CHUNK_ITERS
    rc.CHUNK_ITERS = CHUNK_ITERS
    os.environ["REPRO_CHUNK_ITERS"] = str(CHUNK_ITERS)
    payload: dict = {"smoke": True, "n_iters": n,
                     "chunk_iters": CHUNK_ITERS}
    try:
        # ground truth: clean library run, no store, no daemon
        rc.clear()
        rc.configure(enabled=False)
        ref = _run(n)

        wk = _drill_worker_kill(rc, work, n, ref)
        rc.clear()
        rc.configure(enabled=False)
        ref_half = _run(n // 2)  # the store-damage drill runs shorter
        cr = _drill_corrupt_record(rc, work, n // 2, ref_half)
        dr = _drill_daemon_restart(rc, work, n, ref)
        payload.update({
            "worker_kill": wk, "corrupt_record": cr,
            "daemon_restart": dr,
            "identical": (wk["identical"] and cr["identical"]
                          and dr["identical"]),
            "exactly_once": all(
                d["records"] == d["expect_records"]
                for d in (wk, cr, dr)),
        })
    finally:
        rc.clear()
        rc.configure(enabled=False)
        rc.CHUNK_ITERS = old_ci
        os.environ.pop("REPRO_CHUNK_ITERS", None)
        shutil.rmtree(work, ignore_errors=True)
    payload["wall_s"] = time.perf_counter() - t0

    from .sweep import update_bench
    update_bench("chaos", payload, out_path)
    print(f"chaos smoke: identical={payload.get('identical')} "
          f"exactly_once={payload.get('exactly_once')} "
          f"worker_restarts="
          f"{payload.get('worker_kill', {}).get('worker_restarts')} "
          f"quarantined="
          f"{payload.get('corrupt_record', {}).get('quarantined')} "
          f"resumed_jobs="
          f"{payload.get('daemon_restart', {}).get('resumed_jobs')} "
          f"({payload['wall_s']:.1f}s); wrote {out_path}")
    if not (payload.get("identical") and payload.get("exactly_once")):
        raise SystemExit("chaos smoke FAILED: " + json.dumps(payload))
    return payload


def main() -> dict:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--out", default=BENCH_PATH)
    ap.add_argument("--n-iters", type=int, default=5000)
    a, _ = ap.parse_known_args()
    return run_smoke(out_path=a.out, n=a.n_iters)


if __name__ == "__main__":
    main()
