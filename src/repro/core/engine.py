"""The backend-switchable resolution engine.

The simulator's hot loops — the "last N distinct lines" recency-stack
monoid, the segmented N-way LRU replay, and the wavefront solver's
running-max sweeps — are all scan-shaped: exactly the computation the
paper's dataflow template (and this repo's jax_pallas stack) pipelines.
This module holds one implementation of each kernel per backend and a
tiny selection layer:

* ``REPRO_ENGINE=auto|numpy|jax`` picks the backend process-wide
  (``auto`` is the default: jitted JAX when an accelerator backend is
  present, numpy on plain CPU hosts where XLA's log-depth scans lose to
  the cache-friendly serial forms);
* :func:`use` overrides it per call (the ``engine=`` keyword on the
  ``simulate_*`` entry points), :func:`select` process-wide;
* explicit ``jax`` uses the jitted kernels even on CPU — they are
  bit-identical by construction (integer max/compare only, no floats),
  which is what the CI ``REPRO_ENGINE=jax`` lane asserts.  An explicit
  ``jax`` on a host where jax does not import raises.

Every kernel here is exact integer arithmetic; backends may only differ
in wall clock, never in results.  Sizes below the ``JIT_MIN_*``
thresholds keep the numpy form even under ``jax`` selection *when
auto-selected* — dispatch + host-transfer overhead dominates tiny
calls — but an explicit selection is honoured as asked.

The per-phase walls (:func:`phase` / :func:`walls`) and the per-kernel
device dispatch counts (:func:`dispatches`, keyed by the platform each
result came back from) are names for the process's one span-and-counter
registry, :mod:`repro.trace`: the chunk-graph master merges its
workers' walls into it, and every device call of a kernel here is a
``roundtrip`` span with an ``elements.<kernel>@<platform>`` counter of
the unpadded elements it carried.
"""

from __future__ import annotations

import contextlib
import os
import threading

import numpy as np

from .. import trace as _trace

__all__ = [
    "current", "select", "use", "jax_modules",
    "phase", "walls", "reset_walls", "merge_walls",
    "dispatches", "reset_dispatches", "CHILD_ENV", "cpu_children",
    "running_max", "nway_core", "lru_insert", "stack_compose",
]

_VALID = ("auto", "numpy", "jax")

#: per-call / process-wide override installed by :func:`use` /
#: :func:`select`; ``None`` defers to ``$REPRO_ENGINE``
_forced: str | None = None

#: cached ``(jax, jax.numpy, jax.lax)`` triple, ``False`` when the
#: import failed — one attempt per process
_jax_mods = None

#: below this many scan elements the numpy running max is kept even on
#: the jax engine when auto-selected (dispatch overhead > kernel time)
JIT_MIN_ELEMS = 1 << 15

#: below this many segments the numpy N-way core is kept likewise
JIT_MIN_SEGMENTS = 1 << 9


def _env_choice() -> str:
    v = (os.environ.get("REPRO_ENGINE") or "auto").strip().lower()
    return v if v in _VALID else "auto"


def jax_modules():
    """``(jax, jnp, lax)``, or ``None`` when jax is not importable.

    Importing here never touches global jax config: the engine's
    kernels run under a *scoped* :func:`_x64` context instead (see
    there for why 64-bit mode is mandatory for them but must not leak
    into the process default).
    """
    global _jax_mods
    if _jax_mods is None:
        try:
            import jax
            import jax.numpy as jnp
            from jax import lax
            _jax_mods = (jax, jnp, lax)
        except Exception:
            _jax_mods = False
    return _jax_mods or None


def _x64():
    """Scoped 64-bit mode for one engine kernel call.

    x64 is mandatory for the kernels: carried cache tags exceed
    ``2**31`` on large address spaces (there is a regression test),
    and jax silently truncates int64 arrays to int32 without it.  But
    flipping ``jax_enable_x64`` process-wide breaks code that relies
    on jax's default 32-bit weak typing (mixed int32/int64 index
    errors in the model stack), so the engine enables it around
    exactly its own traces and calls — jit caches key on the flag, so
    scoped-x64 traces never collide with the host program's."""
    return jax_modules()[0].enable_x64(True)


def current() -> str:
    """The engine this call site resolves to: ``"numpy"`` or ``"jax"``.

    Order: :func:`use`/:func:`select` override, then ``$REPRO_ENGINE``,
    then ``auto`` — which picks jax only when jax imports *and* its
    default backend is an accelerator (on CPU the serial numpy scans
    beat XLA's log-depth ones; see docs/engine.md for the measurement).
    An explicit jax selection without an importable jax raises.
    """
    choice = _forced or _env_choice()
    if choice == "auto":
        m = jax_modules()
        if m is not None and m[0].default_backend() != "cpu":
            return "jax"
        return "numpy"
    if choice == "jax" and jax_modules() is None:
        raise RuntimeError("engine 'jax' selected but jax does not import")
    return choice


#: the environment of every process the simulator starts (the chunk-
#: graph pool, the daemon and its workers): the parent may hold the
#: chip, and a chip belongs to one process, so children resolve on CPU
#: with numpy whatever the parent runs
CHILD_ENV = {"JAX_PLATFORMS": "cpu", "REPRO_ENGINE": "numpy"}

_child_lock = threading.Lock()


@contextlib.contextmanager
def cpu_children():
    """Scope in which ``multiprocessing`` spawns (which copy this
    process's environment) start their children with :data:`CHILD_ENV`;
    the parent's environment is restored on exit."""
    with _child_lock:
        saved = {k: os.environ.get(k) for k in CHILD_ENV}
        os.environ.update(CHILD_ENV)
        try:
            yield
        finally:
            for k, v in saved.items():
                if v is None:
                    os.environ.pop(k, None)
                else:
                    os.environ[k] = v


def _explicit() -> bool:
    """True when jax was asked for by name (override or env) rather
    than auto-selected — explicit selections bypass the size
    thresholds so the CI lane exercises the jitted kernels on every
    call size."""
    return (_forced or _env_choice()) == "jax"


def select(name: str | None) -> None:
    """Process-wide engine selection (``None`` reverts to the env)."""
    global _forced
    if name is not None and name not in _VALID:
        raise ValueError(f"unknown engine {name!r}; pick from {_VALID}")
    _forced = name


@contextlib.contextmanager
def use(name: str | None):
    """Scoped engine override — the ``engine=`` keyword of the
    ``simulate_*`` entry points.  ``None`` is a no-op."""
    if name is None:
        yield
        return
    if name not in _VALID:
        raise ValueError(f"unknown engine {name!r}; pick from {_VALID}")
    global _forced
    prev = _forced
    _forced = name
    try:
        yield
    finally:
        _forced = prev


# ---------------------------------------------------------------------------
# Per-phase walls and dispatch counts: names for the repro.trace registry
# ---------------------------------------------------------------------------

#: ``with phase("replay"): ...`` — effect / replay / fold / solve are the
#: canonical phases, ``windows`` and ``roundtrip`` nest inside them
phase = _trace.span
walls = _trace.walls
merge_walls = _trace.merge

#: prefix of the dispatch counters in the registry
_DISPATCH = "dispatch."


#: both clear the whole registry, spans and counters: callers reset the
#: two together where a measurement starts
reset_walls = reset_dispatches = _trace.reset


def _dispatched(name: str, out, elements: int) -> None:
    """Count one device call of kernel ``name`` and the ``elements`` it
    carried, keyed ``"name@platform"`` with the platform read off the
    result, so a run that claims the chip can show its kernels came
    back from it."""
    plat = next(iter(out.devices())).platform
    _trace.count(f"{_DISPATCH}{name}@{plat}")
    _trace.count(f"elements.{name}@{plat}", elements)


def dispatches() -> dict[str, int]:
    """``{"kernel@platform": device calls}`` in this process."""
    return {k[len(_DISPATCH):]: v for k, v in _trace.counts().items()
            if k.startswith(_DISPATCH)}


# ---------------------------------------------------------------------------
# Running max (the wavefront solver's serial recurrence)
# ---------------------------------------------------------------------------

#: block width of the dominated-block numpy running max — big enough
#: that the per-block bookkeeping vanishes, small enough that one block
#: sits in L1
_RMAX_BLOCK = 4096

_cummax_jit = None


def _running_max_np(a: np.ndarray) -> np.ndarray:
    """In-place inclusive running max, skipping dominated blocks.

    ``np.maximum.accumulate`` is a serial scalar loop.  The solver's
    arrays are ``b - cumsum(c)`` shapes that trend *down* (the paper's
    pipelines are mostly self-recurrence-bound), so most blocks never
    beat the carry from the left: per-block maxima are computed
    vectorized, blocks whose max is dominated by the incoming carry are
    filled with the carry constant, and only the rest pay the scalar
    accumulate — ~8x on trending data, bounded regression (~1.1x) on
    monotonically increasing data.
    """
    n = a.size
    B = _RMAX_BLOCK
    if n < 2 * B or not a.flags.c_contiguous:
        np.maximum.accumulate(a, out=a)
        return a
    nb = n // B
    m2 = a[:nb * B].reshape(nb, B)
    M = m2.max(axis=1)
    C = np.maximum.accumulate(M)
    np.maximum.accumulate(m2[0], out=m2[0])
    need = np.nonzero(M[1:] > C[:-1])[0] + 1
    for i in need:
        row = m2[i]
        np.maximum.accumulate(row, out=row)
        np.maximum(row, C[i - 1], out=row)
    dom = np.ones(nb, dtype=bool)
    dom[0] = False
    dom[need] = False
    if dom.any():
        m2[dom] = C[np.nonzero(dom)[0] - 1, None]
    tail = a[nb * B:]
    if tail.size:
        np.maximum.accumulate(tail, out=tail)
        np.maximum(tail, C[-1], out=tail)
    return a


def running_max(a: np.ndarray) -> np.ndarray:
    """In-place inclusive running maximum of a 1-D integer array.

    On the jax engine (above the dispatch threshold) an int32 array on
    a TPU goes to :func:`pallas_running_max`, and everything else (int64,
    or another backend) to the jitted ``lax.cummax``; the choice follows
    the dtype and the backend, never a caught error.  Below the
    threshold, and on the numpy engine, the dominated-block numpy form
    runs.  All are exact, so results never depend on the engine.
    """
    if a.size >= JIT_MIN_ELEMS and current() == "jax":
        jx, jnp, lax = jax_modules()
        if a.dtype == np.int32 and jx.default_backend() == "tpu":
            a[:] = pallas_running_max(a)
            return a
        global _cummax_jit
        if _cummax_jit is None:
            def cummax(x):          # the trace's module: jit_cummax
                return lax.cummax(x, axis=0)
            _cummax_jit = jx.jit(cummax)
        with _x64(), _trace.span("roundtrip", kernel="cummax"):
            out = _cummax_jit(a)
            a[:] = np.asarray(out)
        _dispatched("cummax", out, a.size)
        return a
    return _running_max_np(a)


# ---------------------------------------------------------------------------
# The recency-stack monoid (shared by both backends)
# ---------------------------------------------------------------------------

def lru_insert(stk: np.ndarray, x: np.ndarray) -> np.ndarray:
    """One LRU step over per-row recency stacks.

    ``stk`` is ``(rows, ways)`` with slot 0 the MRU tag (−1 = empty);
    ``x`` is one tag per row (−2 = inactive row this round).  Returns
    the updated stacks: a present tag rotates to the front, an absent
    one shifts the whole stack (evicting the last slot).
    """
    ways = stk.shape[1]
    cmp = stk == x[:, None]
    found = cmp.any(axis=1)
    # rotate depth: the hit way, or the whole stack on a miss
    j = np.where(found, np.argmax(cmp, axis=1), ways - 1)
    j[x == -2] = -1  # inactive rows rotate nothing
    shifted = np.empty_like(stk)
    shifted[:, 1:] = stk[:, :-1]
    shifted[:, 0] = x
    return np.where(np.arange(ways) <= j[:, None], shifted, stk)


def stack_compose(older: np.ndarray, newer: np.ndarray) -> np.ndarray:
    """Compose two recency stacks: ``newer`` applied after ``older``.

    The "last N distinct lines" monoid: the result is ``newer``'s tags
    followed by ``older``'s tags not already present, truncated to N.
    Associative — tags pushed past slot N can never resurface.
    """
    rows, ways = newer.shape
    nb = (newer >= 0).sum(axis=1)
    in_newer = (older[:, :, None] == newer[:, None, :]).any(axis=2)
    keep = (older >= 0) & ~in_newer
    tgt = nb[:, None] + np.cumsum(keep, axis=1) - 1
    out = newer.copy()
    mask = keep & (tgt < ways)
    r_idx = np.broadcast_to(np.arange(rows)[:, None], tgt.shape)
    out[r_idx[mask], tgt[mask]] = older[mask]
    return out


# ---------------------------------------------------------------------------
# The segmented N-way replay core
# ---------------------------------------------------------------------------

def _nway_core_np(T: np.ndarray, seg_grp: np.ndarray,
                  seg_first: np.ndarray, carried: np.ndarray,
                  max_run: int) -> tuple[np.ndarray, np.ndarray]:
    """numpy reference of :func:`nway_core` (see there for the
    contract) — pass A, the segmented Hillis–Steele compose, pass B."""
    W, G = T.shape
    ways = carried.shape[1]
    # pass A: per-segment own stacks, replayed from empty
    stk = np.full((G, ways), -1, dtype=T.dtype)
    for r in range(W):
        stk = lru_insert(stk, T[r])
    # incoming[g] = carried ∘ own[first..g-1]: inclusive segmented scan
    # over E = [carried at set-first segments, own[g-1] elsewhere]
    E = np.empty_like(stk)
    E[1:] = stk[:-1]
    E[seg_first] = carried[seg_grp[seg_first]]
    d = 1
    while d < max_run:
        composed = stack_compose(E[:-d], E[d:])
        valid = seg_grp[d:] == seg_grp[:-d]
        E[d:] = np.where(valid[:, None], composed, E[d:])
        d *= 2
    # pass B: replay from the incoming stacks, recording hits
    HIT = np.empty((W, G), dtype=bool)
    stk = E
    for r in range(W):
        x = T[r]
        HIT[r] = (stk == x[:, None]).any(axis=1) & (x != -2)
        stk = lru_insert(stk, x)
    return HIT, stk


_nway_jit = None


def _build_nway_jit():
    """The jitted N-way core.  One traced function; XLA's own cache
    keys on shapes, which the caller pads to powers of two so a long
    run compiles a handful of variants, not one per chunk."""
    jx, jnp, lax = jax_modules()

    def insert(stk, x):
        ways = stk.shape[1]
        cmp = stk == x[:, None]
        found = cmp.any(axis=1)
        j = jnp.where(found, jnp.argmax(cmp, axis=1), ways - 1)
        j = jnp.where(x == -2, -1, j)
        shifted = jnp.concatenate([x[:, None], stk[:, :-1]], axis=1)
        return jnp.where(jnp.arange(ways)[None, :] <= j[:, None],
                         shifted, stk)

    def compose(older, newer):
        # the scatter of the numpy form recast as a gather (XLA-
        # friendly): out[:, w] takes older's unique source column with
        # keep & tgt == w, else newer[:, w]
        ways = newer.shape[1]
        nb = (newer >= 0).sum(axis=1)
        in_newer = (older[:, :, None] == newer[:, None, :]).any(axis=2)
        keep = (older >= 0) & ~in_newer
        tgt = nb[:, None] + jnp.cumsum(keep, axis=1) - 1
        sel = keep & (tgt < ways)
        hitm = sel[:, None, :] & (tgt[:, None, :]
                                  == jnp.arange(ways)[None, :, None])
        has = hitm.any(axis=2)
        src = jnp.argmax(hitm, axis=2)
        vals = jnp.take_along_axis(older, src, axis=1)
        return jnp.where(has, vals, newer)

    # named for the trace's module, jit_nway_core
    def nway_core(T, seg_grp, seg_first, carried, run):
        W, G = T.shape
        ways = carried.shape[1]
        stk0 = jnp.full((G, ways), -1, T.dtype)
        own = lax.fori_loop(0, W, lambda r, s: insert(s, T[r]), stk0)
        E = jnp.concatenate([own[:1], own[:-1]], axis=0)
        idx = jnp.clip(seg_grp, 0, carried.shape[0] - 1)
        E = jnp.where(seg_first[:, None], carried[idx], E)
        rows = jnp.arange(G)

        def body(c):
            d, E = c
            older = jnp.roll(E, d, axis=0)
            valid = (jnp.roll(seg_grp, d) == seg_grp) & (rows >= d)
            E = jnp.where(valid[:, None], compose(older, E), E)
            return d * 2, E

        _, E = lax.while_loop(lambda c: c[0] < run, body,
                              (jnp.int64(1), E))

        def bodyB(r, c):
            stk, HIT = c
            x = T[r]
            h = (stk == x[:, None]).any(axis=1) & (x != -2)
            return insert(stk, x), HIT.at[r].set(h)

        stk, HIT = lax.fori_loop(
            0, W, bodyB, (E, jnp.zeros((W, G), dtype=bool)))
        return HIT, stk

    return jx.jit(nway_core)


def _pow2(n: int, floor: int = 16) -> int:
    return max(floor, 1 << (max(1, n) - 1).bit_length())


def _nway_core_jax(T, seg_grp, seg_first, carried, max_run):
    """Pad to power-of-two shapes (bounding recompiles) and run the
    jitted core; padding segments are inert (tag −2 rows, distinct
    negative segment ids, never set-first)."""
    global _nway_jit
    if _nway_jit is None:
        _nway_jit = _build_nway_jit()
    W, G = T.shape
    ways = carried.shape[1]
    Gp = _pow2(G)
    Cp = _pow2(len(carried), 1)
    if Gp != G:
        Tp = np.full((W, Gp), -2, dtype=T.dtype)
        Tp[:, :G] = T
        sg = np.empty(Gp, dtype=seg_grp.dtype)
        sg[:G] = seg_grp
        sg[G:] = -np.arange(1, Gp - G + 1, dtype=seg_grp.dtype)
        sf = np.zeros(Gp, dtype=bool)
        sf[:G] = seg_first
    else:
        Tp, sg, sf = T, seg_grp, seg_first
    if Cp != len(carried):
        cp = np.full((Cp, ways), -1, dtype=carried.dtype)
        cp[:len(carried)] = carried
    else:
        cp = carried
    with _x64(), _trace.span("roundtrip", kernel="nway"):
        HIT, stk = _nway_jit(Tp, sg, sf, cp, max_run)
        out = np.asarray(HIT)[:, :G], np.asarray(stk)[:G]
    _dispatched("nway", HIT, T.size)
    return out


def nway_core(T: np.ndarray, seg_grp: np.ndarray, seg_first: np.ndarray,
              carried: np.ndarray, max_run: int,
              ) -> tuple[np.ndarray, np.ndarray]:
    """The segmented N-way LRU replay over pre-cut segments.

    ``T`` is ``(W, G)``: per-segment tag columns, −2 where inactive;
    ``seg_grp`` maps each segment to its touched-set row in ``carried``
    (the incoming recency stacks, MRU first); ``seg_first`` flags each
    set's first segment; ``max_run`` is the longest per-set segment
    run.  Returns ``(HIT, final)`` — per-position hit flags and each
    segment's outgoing stack (the caller keeps only each set's last).

    Backends are bit-identical: the jax path runs the same pass A /
    segmented-compose / pass B algorithm under ``jit`` (integer
    compares and shifts only).
    """
    G = T.shape[1]
    if current() == "jax" and (G >= JIT_MIN_SEGMENTS or _explicit()):
        return _nway_core_jax(T, seg_grp, seg_first, carried, max_run)
    return _nway_core_np(T, seg_grp, seg_first, carried, max_run)


# ---------------------------------------------------------------------------
# Pallas running max (TPU; the CPU path never reaches this)
# ---------------------------------------------------------------------------

#: lanes of one vreg row; blocks are (rows, 128) int32, rows % 8 == 0
_LANES = 128

#: rows per grid step: 512 x 128 int32 = 256 KiB per block
_RMAX_ROWS = 512

_pallas_rmax = {}


def _build_pallas_rmax(rows: int, nb: int, interpret: bool):
    """The jitted Pallas running max over ``nb`` blocks of ``(rows, 128)``
    int32 values in row-major order.

    Each grid step scans its block in two Hillis–Steele passes —
    across the 128 lanes of every row, then across the block's rows of
    row totals — with ``pltpu.roll`` shifts masked by an iota, and folds
    in the carry: the running max of all earlier blocks, kept in a VMEM
    scratch tile.  Grid steps run in order ("arbitrary"), so the carry
    is exact.
    """
    jx, jnp, lax = jax_modules()
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    lo = np.iinfo(np.int32).min
    shape = (rows, _LANES)

    def prefix(x, axis: int, width: int):
        idx = lax.broadcasted_iota(jnp.int32, shape, axis)
        k = 1
        while k < width:
            x = jnp.maximum(
                x, jnp.where(idx >= k, pltpu.roll(x, k, axis), lo))
            k *= 2
        return x

    def kernel(x_ref, o_ref, carry_ref):
        @pl.when(pl.program_id(0) == 0)
        def _seed():
            carry_ref[...] = jnp.full(carry_ref.shape, lo, jnp.int32)

        x = prefix(x_ref[...], 1, _LANES)
        tot = prefix(jnp.broadcast_to(
            jnp.max(x, axis=1, keepdims=True), shape), 0, rows)
        row = lax.broadcasted_iota(jnp.int32, shape, 0)
        before = jnp.where(row >= 1, pltpu.roll(tot, 1, 0), lo)
        out = jnp.maximum(jnp.maximum(x, before), carry_ref[0:1, :])
        o_ref[...] = out
        carry_ref[...] = jnp.broadcast_to(
            jnp.max(out[rows - 1:rows, :], axis=1, keepdims=True),
            carry_ref.shape)

    call = pl.pallas_call(
        kernel,
        grid=(nb,),
        in_specs=[pl.BlockSpec(shape, lambda i: (i, 0))],
        out_specs=pl.BlockSpec(shape, lambda i: (i, 0)),
        out_shape=jx.ShapeDtypeStruct((nb * rows, _LANES), jnp.int32),
        scratch_shapes=[pltpu.VMEM((8, _LANES), jnp.int32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="running_max",
    )

    def running_max(x):             # the trace's module: jit_running_max
        return call(x)

    return jx.jit(running_max)


def pallas_running_max(x: np.ndarray, *, block_rows: int = _RMAX_ROWS,
                       interpret: bool = False) -> np.ndarray:
    """Inclusive running max of a 1-D int32 array as a Pallas grid kernel.

    The array is padded with the int32 minimum to a power-of-two number
    of ``(rows, 128)`` blocks (bounding recompiles) and scanned on the
    device.  int32 is the kernel's only dtype: the wavefront solver
    hands over chunk-relative values, which fit int32 whenever the
    chunk's range allows (``_LaneSolver``).  ``interpret=True`` runs the
    kernel on CPU for tests."""
    if x.dtype != np.int32:
        raise TypeError(f"pallas_running_max takes int32, got {x.dtype}")
    n = x.shape[0]
    need = -(-max(n, 1) // _LANES)
    rows = min(block_rows, -(-need // 8) * 8)
    nb = _pow2(-(-need // rows), 1)
    key = (rows, nb, interpret)
    fn = _pallas_rmax.get(key)
    if fn is None:
        fn = _pallas_rmax[key] = _build_pallas_rmax(rows, nb, interpret)
    xp = np.full(nb * rows * _LANES, np.iinfo(np.int32).min, np.int32)
    xp[:n] = x
    with _trace.span("roundtrip", kernel="pallas_running_max"):
        out = fn(xp.reshape(nb * rows, _LANES))
        res = np.asarray(out).reshape(-1)[:n]
    _dispatched("pallas_running_max", out, n)
    return res
