"""The chunk graph: sharded trace resolution on a process pool.

The streaming resolver of :mod:`repro.core.simulator` visits a kernel's
iteration range chunk by chunk with *carried* state — the on-PL cache's
replacement state and each memory model's RNG draw position thread
serially through the chunks, so one core does all the work while the
rest idle behind its memory bandwidth.  This module breaks that chain
into a **chunk graph** whose expensive nodes are independent:

* **Phase A+B (parallel, fused)** — each chunk replays **once** from
  an empty cache
  (:meth:`~repro.core.simulator._SharedResolver.chunk_effects_fused`),
  producing both its *own* cache effect — the per-set "last N distinct
  lines" recency stacks, an associative monoid needing no incoming
  state — and its hit flags up to a small boundary-ambiguity table
  (the chunk's first ``ways`` first-touches per set, the only verdicts
  an incoming state can change).  Effects are also persisted as
  rescache *effect records* (``<key>.eNNNNN.npz``) so a re-shard
  composes stored effects instead of waiting for phase-A messages.
* **Compose (master, cheap)** — a serial scan over the tiny per-chunk
  effect snapshots (:func:`~repro.core.simulator.compose_stacks`)
  yields every chunk's exact *incoming* cache state.
* **Finalize (parallel, tiny)** — the worker patches its chunk's
  ambiguous verdicts against the incoming state
  (:meth:`~repro.core.simulator._SharedResolver.finalize_replay`) —
  bit-identical to a full warm replay, at the cost of a few hundred
  boundary lookups.
* **Phase C (parallel)** — backing-store draws.  The draw stream is
  position-exact (one PCG64 double per draw), so the master turns the
  per-chunk backing trips into per-chunk draw *offsets* and the worker
  fast-forwards a seeded RNG with ``advance`` — draw-for-draw identical
  to the streaming pass.  The per-op latency matrices are committed to
  the rescache as v3 chunk records (or handed back inline when the
  artifact is above the size cap).

Two schedulers run this protocol — the library pool of
:func:`simulate_dataflow_sharded` (``simulate_dataflow_many(...,
workers=N)``) and the resolution daemon of :mod:`repro.serve.daemon` —
and both are built from the same two pieces here:

* :func:`worker_main` — **the one worker loop**.  It takes ``("job",
  jid, payload)``, ``("task", jid, k, lo, hi)``, ``("state", jid, k,
  state)``, ``("draws", jid, k, offsets)``, ``("forget", jid)`` and
  ``("stop",)``, and answers ``effect``, ``replay``, ``done`` or
  ``error``, each carrying ``(wid, jid, k)``.  A :class:`ChunkJob`
  holds one job's resolver and record writers and the scratch of each
  chunk in flight, so the phases of different chunks and jobs may
  interleave on one worker.  :func:`start_worker` gives each worker
  its own task queue and its own result pipe, which the scheduler reads
  with :func:`recv_replies`: a worker killed while writing a reply
  (an OOM kill) tears only its own pipe, which reads as end of file and
  is dropped, and no other worker's replies wait behind it.  A worker
  whose scheduler died exits on its own.
* :class:`ChunkSequencer` — **the one master-side sequencer**: effects
  in, incoming states out in chunk order; replay deltas in, draw
  offsets and hit/miss cumulants out in chunk order; and the log of
  every message sent for a chunk in flight, which :meth:`resend
  <ChunkSequencer.resend>` replays verbatim to another worker
  (resolution is deterministic, so a resend is bit-identical).

Each scheduler keeps its own policy: where a chunk runs, when to
speculate, the retry budget, and — in the pool — the in-order fold and
wavefront solve overlapped with the workers resolving ahead (the
daemon streams records to its clients instead, which fold and solve).

The result is bit-identical to the streaming engine (same canonical
access order, same replacement decisions, same draw stream — asserted
access-for-access in tests); only the wall clock changes.  Served and
resumed prefixes compose with sharding: chunks below the store's resume
point never reach the pool.

Workers receive the stage list via ``cloudpickle`` (the paper kernels'
window generators are closures, which plain pickle rejects); when
``cloudpickle`` is unavailable or the payload will not serialize, the
caller transparently falls back to the streaming path.
"""

from __future__ import annotations

import multiprocessing
import multiprocessing.connection
import os
import queue
import time
import traceback
from typing import Mapping, Sequence

import numpy as np

from . import engine as _eng
from . import rescache as _rc
from .simulator import (SimResult, _cache_group_key,
                        _dataflow_many_stream, _lat_itemsize, _LaneSolver,
                        _OpFolder, _ResolutionPlan, _ServeLost,
                        _SharedResolver, compose_stacks)

#: Outstanding chunks per worker before a scheduler stops dispatching
#: (bounds queue memory: at most ``workers * _WINDOW`` unconsumed
#: per-op matrices are in flight).
_WINDOW = 2

#: Chunk re-dispatches tolerated per run after worker deaths (OOM
#: kills, segfaults) before the run hard-fails instead of looping —
#: every retry is counted in ``rescache.census()["worker_retries"]``.
RETRY_BUDGET = 3

#: Completed pool executions in this process — lets tests assert the
#: sharded path actually engaged rather than silently falling back to
#: the streaming engine (missing cloudpickle, too few chunks, …).
_POOL_RUNS = 0


def default_workers(*, cpus: int | None = None, jobs: int = 1,
                    explicit: int | None = None,
                    full: bool = True) -> int:
    """The ``--workers`` default heuristic, shared by every benchmark
    driver: the fused effect+replay pass made sharding break even on
    2 cores (``worker_scaling`` in BENCH_sim.json; it recorded 0.19×
    when phases A and B were two separate replays), but process spawn
    and payload pickling still cost seconds that only amortize when
    several cores actually run concurrently — so auto-sharding keeps
    falling back to the streaming engine below 4 cores unless the user
    passed an explicit count.  ``jobs`` is the concurrent task-pool
    width the workers share the cores with."""
    if explicit is not None:
        return max(1, explicit)
    if cpus is None:
        cpus = multiprocessing.cpu_count()
    if not full or cpus < 4:
        return 1
    return max(2, cpus // max(1, jobs))


def default_speculate_after_s() -> float:
    """Seconds a chunk may run before a scheduler dispatches a
    speculative duplicate: ``$REPRO_SPECULATE_AFTER_S``, default 30
    (also when unset, empty or unparsable); 0 disables speculation."""
    try:
        return float(os.environ.get("REPRO_SPECULATE_AFTER_S") or 30)
    except ValueError:
        return 30.0


def rescache_cfg() -> dict:
    """This process's store configuration, which :func:`worker_main`
    re-applies in a spawned worker."""
    return {
        "enabled": _rc._cfg.enabled,
        "directory": _rc._dir(),
        "memory_mb": _rc._cfg.memory_mb,
        "artifact_mb": _rc._cfg.artifact_mb,
        "disk_mb": _rc._cfg.disk_mb,
    }


def _compose_state(older, newer):
    """Compose two per-geometry state maps (``None`` = empty cache)."""
    if older is None:
        return newer
    out = {}
    for geo, (stk_new, mt_new) in newer.items():
        old = older.get(geo)
        if old is None:
            out[geo] = (stk_new, mt_new)
        else:
            out[geo] = (compose_stacks(old[0], stk_new),
                        max(old[1], mt_new))
    return out


# ---------------------------------------------------------------------------
# Worker side
# ---------------------------------------------------------------------------

class ChunkJob:
    """One job on one worker: the shared resolver for the payload's live
    models, their v3 chunk writers and effect-record keys, and the
    :class:`~repro.core.simulator._ChunkScratch` of each chunk between
    its phases, keyed by chunk index.

    ``payload`` holds ``stages``, ``mems`` (the live models), ``seed``,
    ``n_iters``, ``keys`` (model -> v3 key or ``None``) and optionally
    ``capture`` (build the hit/visit planes; default on)."""

    def __init__(self, payload: Mapping):
        self.mems = payload["mems"]
        r = self.resolver = _SharedResolver(
            payload["stages"], self.mems, payload["seed"],
            capture=payload.get("capture", True))
        keys = {mn: k for mn, k in payload["keys"].items()
                if k is not None}
        writers = {mn: _rc.ChunkWriter(
            key, r.K, payload["n_iters"],
            itemsize=_lat_itemsize(self.mems[mn]))
            for mn, key in keys.items()}
        self.writers = {mn: w for mn, w in writers.items() if not w.dead}
        self.effect_keys = {mn: key for mn, key in keys.items()
                            if r.cache_keys[mn] is not None}
        self.scratch: dict[int, object] = {}

    def effect(self, k: int, lo: int, hi: int) -> tuple[dict, int]:
        """Phases A+B fused: one empty-cache replay gives the chunk's
        own effect (persisted as effect records) and its hit flags up
        to the ambiguity table :meth:`finalize` patches."""
        effects, n_addrs, self.scratch[k] = \
            self.resolver.chunk_effects_fused(lo, hi)
        with _eng.phase("effect"):
            for mn, ekey in self.effect_keys.items():
                geo = self.resolver.cache_keys[mn]
                if geo in effects:
                    _rc.put_effect(ekey, k, effects[geo], n_addrs)
        return effects, n_addrs

    def finalize(self, k: int, state: Mapping | None) -> dict:
        """Patch the chunk's verdicts against its composed incoming
        state; returns the per-geometry replay deltas."""
        return self.resolver.finalize_replay(state, self.scratch[k])

    def draws(self, k: int, offsets: Mapping) -> tuple[dict, dict]:
        """Phase C: position each model's draw stream at its offset,
        materialize the latencies and commit the chunk record (or hand
        the matrix back inline past the artifact cap).  Returns
        ``(cums, inline)`` per model: the record's cumulative counters,
        and ``None`` or ``{"ops", "hits", "visits"}``."""
        r = self.resolver
        sc = self.scratch.pop(k)
        for mn, off in offsets.items():
            r.import_resume(mn, {}, {"draws": off["base"]})
        r.finish(sc.lo, sc.hi, fold=False, scratch=sc)
        cums: dict[str, dict] = {}
        inline: dict[str, dict | None] = {}
        for mn in self.mems:
            geo = r.cache_keys[mn]
            cum = {"draws": r.draws[mn]}
            states = {}
            if geo is not None:
                cum["hits"] = offsets[mn]["hits_after"]
                cum["misses"] = offsets[mn]["misses_after"]
                cum["max_tag"] = sc.end[geo][1]
                states["cache"] = sc.end[geo][0]
            cums[mn] = cum
            hb = vb = None
            if r.last_hits.get(mn) is not None:
                hb = _rc.pack_flags(r.last_hits[mn])
                vb = _rc.pack_flags(r.last_visits[mn])
            w = self.writers.get(mn)
            if w is not None and k < w.max_chunks:
                w.add(k, sc.hi - sc.lo,
                      np.ascontiguousarray(r.last_ops[mn]),
                      hb, vb, states, cum)
                inline[mn] = None  # the master reads the record
            else:
                inline[mn] = {"ops": _rc.shrink_ops(r.last_ops[mn]),
                              "hits": hb, "visits": vb}
        return cums, inline


def worker_main(wid: int | None, C: int, task_q, result_conn,
                rescache_cfg: dict) -> None:
    """One worker process of either scheduler (see the module
    docstring for its messages).  Every reply is ``(kind, wid, jid, k,
    *payload, wall_s)``: ``effect`` carries ``(effects, n_addrs)``,
    ``replay`` the deltas, ``done`` ``(cums, inline, walls)``; an
    ``error`` carries the traceback instead of a wall, and the worker
    keeps serving its other jobs.  ``wid`` is the daemon's worker slot
    and ``None`` in the library pool, whose fault hooks therefore match
    no ``target`` filter."""
    jid = k = -1
    try:
        import cloudpickle
        from ..runtime import faults
        _rc.configure(**rescache_cfg)
        _rc.CHUNK_ITERS = C
    except Exception:  # noqa: BLE001 — forwarded verbatim
        result_conn.send(("error", wid, jid, k, traceback.format_exc()))
        return
    jobs: dict[int, ChunkJob] = {}
    scheduler = multiprocessing.parent_process()
    while True:
        try:
            m = task_q.get(timeout=1.0)
        except queue.Empty:
            if scheduler is not None and not scheduler.is_alive():
                return  # orphaned (the scheduler was killed): exit
            continue
        op = m[0]
        if op == "stop":
            return
        t0 = time.perf_counter()
        try:
            if op == "job":
                jid = m[1]
                jobs[jid] = ChunkJob(cloudpickle.loads(m[2]))
                continue
            if op == "forget":
                jobs.pop(m[1], None)
                continue
            _, jid, k, *args = m
            job = jobs[jid]
            if op == "task":
                if faults.active():  # chaos: die mid-chunk
                    faults.maybe_kill("worker_kill", worker=wid, chunk=k)
                reply = ("effect", *job.effect(k, *args))
            elif op == "state":
                reply = ("replay", job.finalize(k, *args))
            else:  # "draws"
                if faults.active():
                    # phase C is the heavy phase (draw materialization
                    # + record write): a straggler here stalls the
                    # in-order commit — what speculation absorbs
                    faults.maybe_sleep("straggler", worker=wid, chunk=k)
                reply = ("done", *job.draws(k, *args), _eng.walls())
                _eng.reset_walls()
            result_conn.send((reply[0], wid, jid, k, *reply[1:],
                              time.perf_counter() - t0))
        except Exception:  # noqa: BLE001 — the scheduler fails the job
            result_conn.send(("error", wid, jid, k, traceback.format_exc()))


def start_worker(ctx, wid: int | None, C: int, cfg: dict):
    """Start one :func:`worker_main` process; returns ``(process,
    task_queue, result_connection)``.  The worker holds the only write
    end of its result pipe, so the connection reads end of file once
    the worker is gone."""
    task_q = ctx.Queue()
    reader, writer = ctx.Pipe(duplex=False)
    proc = ctx.Process(target=worker_main,
                       args=(wid, C, task_q, writer, cfg), daemon=True)
    with _eng.cpu_children():
        proc.start()
    writer.close()
    return proc, task_q, reader


def recv_replies(conns: list, timeout: float) -> list[tuple]:
    """The replies ready within ``timeout`` on the workers' result
    connections ``conns`` (one list slot a worker, ``None`` once
    closed), at most one a connection.  A connection at end of file
    (its worker gone, with any reply it was still writing torn) is
    closed and its slot set to ``None``."""
    live = [c for c in conns if c is not None]
    out = []
    for c in multiprocessing.connection.wait(live, timeout):
        try:
            out.append(c.recv())
        except (EOFError, OSError):
            conns[conns.index(c)] = None
            c.close()
    return out


# ---------------------------------------------------------------------------
# Master side
# ---------------------------------------------------------------------------

class ChunkSequencer:
    """The serial scans of one run's chunk graph, on the master.

    ``mems`` are the live models; resolution starts at chunk
    ``first_live`` from ``state`` (geometry -> ``(stacks, max_tag)``, or
    ``None`` for cold caches), ``cum_draws`` (model -> draws consumed)
    and ``geo_cum`` (geometry -> ``(hits, misses)``).  The scheduler
    reports each worker reply (:meth:`effect`, :meth:`replay`,
    :meth:`done`) and sends what :meth:`task` and :meth:`pump` return,
    to the chunk's worker; every message sent for a chunk stays in its
    log until ``done``, for :meth:`resend`.  Duplicates of a chunk's
    replies (a resent or speculative copy) are ignored."""

    def __init__(self, jid: int, mems: Mapping, first_live: int,
                 state: dict | None, cum_draws: Mapping[str, int],
                 geo_cum: Mapping[tuple, tuple[int, int]]):
        self.jid = jid
        self.mems = dict(mems)
        self.geos = {mn: _cache_group_key(m) for mn, m in mems.items()}
        self.state_at: dict[int, dict | None] = {first_live: state}
        self.composed = first_live  # incoming states known up to here
        self.effects: dict[int, dict] = {}
        self.n_addrs: dict[int, int] = {}
        self.deltas: dict[int, dict] = {}
        self.cum_draws = dict(cum_draws)
        self.geo_cum = dict(geo_cum)
        self.state_sent = self.draws_sent = first_live
        self.log: dict[int, list[tuple]] = {}

    def task(self, k: int, lo: int, hi: int) -> tuple:
        """Chunk ``k``'s task message; opens its log."""
        msg = ("task", self.jid, k, lo, hi)
        self.log[k] = [msg]
        return msg

    def effect(self, k: int, effects: dict, n_addrs: int) -> None:
        """A chunk's own effect (a worker's, or a stored effect record):
        compose every incoming state it completes, in chunk order."""
        if k < self.composed or k in self.effects:
            return  # duplicate from a resent chunk
        self.effects[k] = effects
        self.n_addrs[k] = n_addrs
        while self.composed in self.effects:
            k = self.composed
            self.state_at[k + 1] = _compose_state(self.state_at[k],
                                                  self.effects.pop(k))
            self.composed += 1

    def replay(self, k: int, deltas: dict) -> None:
        if k >= self.draws_sent:  # else: a resent chunk's duplicate
            self.deltas[k] = deltas

    def done(self, k: int) -> None:
        self.log.pop(k, None)

    def pump(self, upto: int) -> list[tuple]:
        """The state and draws messages now ready for chunks below
        ``upto`` (the dispatched ones), in chunk order."""
        out = []
        while self.state_sent < upto and self.state_sent in self.state_at:
            k = self.state_sent
            out.append(("state", self.jid, k, self.state_at[k] or {}))
            self.log[k].append(out[-1])
            self.state_sent += 1
        while self.draws_sent < upto and self.draws_sent in self.deltas:
            k = self.draws_sent
            d = self.deltas.pop(k)
            offsets = {}
            for mn, mem in self.mems.items():
                geo = self.geos[mn]
                entry = {"base": self.cum_draws[mn]}
                if mem.backing_hit_rate > 0.0:
                    # draws consumed this chunk: every backing trip
                    # (misses + write-around stores) for cached
                    # models, every participating access otherwise
                    self.cum_draws[mn] += d[geo][2] \
                        if geo is not None else self.n_addrs[k]
                if geo is not None:
                    h, m = self.geo_cum[geo]
                    entry["hits_after"] = h + d[geo][0]
                    entry["misses_after"] = m + d[geo][1]
                offsets[mn] = entry
            for geo, (dh, dm, _) in d.items():
                h, m = self.geo_cum[geo]
                self.geo_cum[geo] = (h + dh, m + dm)
            out.append(("draws", self.jid, k, offsets))
            self.log[k].append(out[-1])
            del self.n_addrs[k]
            self.draws_sent += 1
        # a state snapshot is dead once it was sent and composed into
        # its successor — prune so a thousand-chunk run keeps
        # O(workers) snapshots, not O(chunks)
        for j in [j for j in self.state_at
                  if j < self.state_sent and j + 1 in self.state_at]:
            del self.state_at[j]
        return out

    def in_phase_c(self, k: int) -> bool:
        """Task, state and draws all sent and no ``done`` yet."""
        return len(self.log.get(k, ())) == 3

    def resend(self, k: int) -> list[tuple]:
        """Every message sent so far for chunk ``k`` (not yet done), in
        order — replayed verbatim to a respawned or speculative worker."""
        return list(self.log[k])


def simulate_dataflow_sharded(
    stages: Sequence,
    mems: Mapping[str, object],
    n_iters: int,
    *,
    fifo_depths: Sequence[int],
    freq_mhz: float,
    seed: int,
    workers: int,
    collect_stalls: bool,
    use_rescache: bool | None,
    depth_incremental: bool = True,
):
    """Grid simulation with resolution sharded over ``workers``
    processes — the entry point behind
    ``simulate_dataflow_many(..., workers=N)``.  Falls back to the
    streaming engine whenever sharding cannot help (no live resolution,
    too few chunks) or the stage list will not serialize."""
    mems = dict(mems)

    def _stream(rescache_override):
        try:
            return _dataflow_many_stream(
                stages, mems, n_iters, fifo_depths=fifo_depths,
                freq_mhz=freq_mhz, seed=seed,
                chunk_iters=_rc.CHUNK_ITERS,
                collect_stalls=collect_stalls,
                use_rescache=rescache_override,
                depth_incremental=depth_incremental)
        except _ServeLost:  # raced store eviction: redo cold
            if rescache_override is False:
                raise
            return _dataflow_many_stream(
                stages, mems, n_iters, fifo_depths=fifo_depths,
                freq_mhz=freq_mhz, seed=seed,
                chunk_iters=_rc.CHUNK_ITERS,
                collect_stalls=collect_stalls, use_rescache=False,
                depth_incremental=depth_incremental)

    try:
        plan = _ResolutionPlan("dataflow", stages, mems, seed, n_iters,
                               use_rescache)
    except _ServeLost:
        return _stream(False)
    C = plan.C
    n_chunks = -(-n_iters // C)
    first_live = plan.resume // C
    if not plan.live or n_chunks - first_live < 2 or workers < 2:
        return _stream(use_rescache)
    keys = {mn: plan.keys[mn] for mn in plan.live
            if plan.keys.get(mn) is not None}
    try:
        import cloudpickle
        payload = cloudpickle.dumps({
            "stages": list(stages), "mems": plan.live, "seed": seed,
            "n_iters": n_iters, "keys": keys,
            "capture": bool(plan.writers)})
    except Exception:  # unpicklable traces: shard is impossible
        return _stream(use_rescache)

    W = min(workers, n_chunks - first_live)
    ctx = multiprocessing.get_context("spawn")
    cfg = rescache_cfg()
    task_qs: list = [None] * W
    procs: list = [None] * W
    conns: list = [None] * W

    def spawn(w: int) -> None:
        if conns[w] is not None:
            conns[w].close()  # a dead worker's pipe, torn reply and all
        procs[w], task_qs[w], conns[w] = start_worker(ctx, None, C, cfg)
        task_qs[w].put(("job", 0, payload))

    for w in range(W):
        spawn(w)

    #: chunk -> worker; seeded round-robin
    owner_of: dict[int, int] = {}

    def owner(k: int) -> int:
        return owner_of.setdefault(k, (k - first_live) % W)

    folder = _OpFolder(stages)
    live_cold: set[int] = set()  # live chunks, for the store census
    solvers = {(mn, d): _LaneSolver(stages, d, collect_stalls)
               for mn in mems for d in fifo_depths}
    depth_order = sorted(set(fifo_depths), reverse=True)
    resolver = plan.resolver

    def solve_chunk(k: int, inline) -> None:
        lo = k * C
        hi = min(lo + C, n_iters)
        for mn in mems:
            if mn in plan.served:
                L = plan.served[mn].chunk(lo, hi)
                _rc.note_chunks(served=1)
            elif k < first_live:
                L = plan.live_ops(mn, lo, hi)
                _rc.note_chunks(served=1)
            elif inline[mn] is not None:
                L = inline[mn]["ops"]
            else:
                # refresh: the worker just (re)wrote this record; a
                # stale partial tail may still sit in the master's LRU
                rec = _rc.get_chunk(plan.keys[mn], k, refresh=True)
                if rec is None:
                    raise _ServeLost(
                        f"sharded record {plan.keys[mn]}.c{k} vanished")
                L = rec.ops
            if L.dtype != np.int32:  # widen shrunk records for the fold
                L = L.astype(np.int32)
            res = folder.fold(mems[mn], lo, hi, L)
            if mn not in plan.served and k >= first_live:
                live_cold.add(k)
            warm = None
            for d in depth_order:
                warm = solvers[(mn, d)].solve_chunk(
                    res, warm=warm if depth_incremental else None)

    seq = ChunkSequencer(
        0, plan.live, first_live,
        {geo: sim.export_stacks() for geo, sim in resolver.caches.items()}
        if plan.resume > 0 else None,
        resolver.draws,
        {geo: (sim.hits, sim.misses)
         for geo, sim in resolver.caches.items()})
    # stored effect records seed the state chain ahead of the workers:
    # walk forward from the resume point while every geometry's effect
    # is on disk, so no state waits on a phase-A message for a chunk
    # this store has seen before
    need: dict[tuple, str] = {}
    for mn, key in keys.items():
        if resolver.cache_keys[mn] is not None:
            need.setdefault(resolver.cache_keys[mn], key)
    if need and set(need) == set(resolver.caches):
        for k in range(first_live, n_chunks):
            recs = {geo: _rc.get_effect(ekey, k)
                    for geo, ekey in need.items()}
            if any(r is None for r in recs.values()):
                break
            seq.effect(k, {geo: (r[0], r[1]) for geo, r in recs.items()},
                       next(iter(recs.values()))[2])
    done: dict[int, tuple] = {}
    final_cums: dict[str, dict] = {}
    retries = 0

    # speculative straggler re-dispatch (the StragglerPolicy
    # bounded-staleness rule applied to chunk dispatch): a phase-C
    # chunk whose wall exceeds the SpeculationPolicy threshold is
    # resent in full on a second live worker.  The master's fold
    # stalls at the straggling chunk while its peers drain to idle, so
    # the duplicate lands on an idle worker; the first "done" wins and
    # the loser's replies die on the sequencer's duplicate guards.
    spec_after = default_speculate_after_s()
    spec_policy = None
    if W > 1 and spec_after > 0:
        from ..runtime.fault_tolerance import SpeculationPolicy
        spec_policy = SpeculationPolicy(min_wait_s=spec_after,
                                        max_inflight=max(1, W // 2))
    draws_t: dict[int, float] = {}   # chunk -> phase-C dispatch time
    spec_owner: dict[int, int] = {}  # chunk -> speculative worker
    # the idle-poll interval is also the straggler-detection latency:
    # shrink it when the speculation threshold is below the default
    poll_s = 5.0 if spec_policy is None else \
        min(5.0, max(0.25, spec_policy.min_wait_s / 2))

    dispatched = first_live
    solved = 0
    failure: str | None = None
    try:
        def dispatch_upto(limit: int) -> None:
            nonlocal dispatched
            while dispatched < min(limit, n_chunks):
                k = dispatched
                task_qs[owner(k)].put(
                    seq.task(k, k * C, min((k + 1) * C, n_iters)))
                dispatched += 1

        def pump() -> None:
            for m in seq.pump(dispatched):
                task_qs[owner_of[m[2]]].put(m)
                if m[0] == "draws":
                    draws_t[m[2]] = time.monotonic()

        dispatch_upto(first_live + W * _WINDOW)
        pump()
        # chunks below the resume point solve immediately from records
        while solved < first_live:
            solve_chunk(solved, None)
            solved += 1
        while solved < n_chunks:
            if solved in done:
                cums, inline = done.pop(solved)
                final_cums.update(cums)
                solve_chunk(solved, inline)
                solved += 1
                dispatch_upto(solved + W * _WINDOW)
                pump()
                continue
            msgs = recv_replies(conns, poll_s)
            if not msgs:
                if spec_policy is not None:
                    now = time.monotonic()
                    for k in sorted(draws_t):
                        if (k in spec_owner or k in done
                                or len(spec_owner)
                                >= spec_policy.max_inflight
                                or not spec_policy.overdue(
                                    now - draws_t[k])):
                            continue
                        alts = [w for w in range(W)
                                if w != owner_of.get(k)
                                and procs[w].is_alive()]
                        if not alts:
                            continue
                        w2 = alts[k % len(alts)]
                        for m in seq.resend(k):
                            task_qs[w2].put(m)
                        spec_owner[k] = w2
                        spec_policy.issued += 1
                        _rc.note_speculation()
                dead = [w for w, pr in enumerate(procs)
                        if not pr.is_alive()]
                if not dead:
                    continue
                # died without posting (OOM kill, segfault): respawn
                # the slot and resend its in-flight chunks' messages
                # under a bounded budget
                for k in [k for k, w in spec_owner.items()
                          if w in dead]:
                    spec_owner.pop(k)  # spec copy lost with its worker
                redo = [k for k in range(solved, dispatched)
                        if k not in done and owner_of.get(k) in dead]
                retries += len(redo)
                _rc.note_worker_retries(len(redo))
                if retries > RETRY_BUDGET:
                    failure = (
                        f"worker(s) {dead} exited with code(s) "
                        f"{[procs[w].exitcode for w in dead]}; retry "
                        f"budget exhausted ({retries} > {RETRY_BUDGET})")
                    break
                for w in dead:
                    spawn(w)
                for k in redo:
                    for m in seq.resend(k):
                        task_qs[owner_of[k]].put(m)
                continue
            for msg in msgs:
                kind, _, _, k = msg[:4]
                if kind == "error":
                    failure = msg[4]
                    break
                if kind == "effect":
                    seq.effect(k, msg[4], msg[5])
                elif kind == "replay":
                    seq.replay(k, msg[4])
                elif kind == "done":
                    t0 = draws_t.pop(k, None)
                    if spec_policy is not None:
                        if k in spec_owner:
                            spec_policy.wins += 1  # a duplicate was live
                        if t0 is not None:
                            spec_policy.observe(time.monotonic() - t0)
                    spec_owner.pop(k, None)
                    if k >= solved:
                        if k not in done:  # not a speculative dup
                            _eng.merge_walls(msg[6])
                        done[k] = (msg[4], msg[5])
                        seq.done(k)
            if failure is not None:
                break
            pump()
        if failure is not None:
            raise RuntimeError(
                f"chunk-graph worker failed:\n{failure}")
    except _ServeLost:
        for q in task_qs:
            q.put(("stop",))
        for pr in procs:
            pr.terminate()
        return _stream(False)
    finally:
        for q in task_qs:
            try:
                q.put(("stop",))
            except Exception:
                pass
        for pr in procs:
            pr.join(timeout=5)
            if pr.is_alive():
                pr.terminate()
        for c in conns:
            if c is not None:
                c.close()

    global _POOL_RUNS
    _POOL_RUNS += 1
    _rc.note_chunks(cold=len(live_cold))
    out: dict[tuple[str, int], SimResult] = {}
    for (mn, d), solver in solvers.items():
        if mn in plan.served:
            ch, cm = plan.served[mn].stats_upto(n_iters)
        else:
            cum = final_cums.get(mn, {})
            ch, cm = int(cum.get("hits", 0)), int(cum.get("misses", 0))
        out[(mn, d)] = SimResult("dataflow", solver.last_finish, n_iters,
                                 freq_mhz, solver.stall, ch, cm)
    return out
