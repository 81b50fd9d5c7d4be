"""The compiler driver: one entry point for the paper's whole flow.

``compile(fn, *example_args, options=...)`` runs the pass pipeline
(trace → memdep → partition → rewrite → decouple → schedule) and returns a
:class:`Compiled` artifact; ``dataflow_jit`` is the decorator form that
compiles lazily on first call per argument shape (like ``jax.jit``).

Compilation results are cached in memory, keyed on the traced jaxpr
(structure + closed-over constants), the example avals, the options, and
the pipeline structure: recompiling the same function with the same
options is a cache hit returning the *same* ``Compiled`` object.
"""

from __future__ import annotations

import inspect
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from .. import trace
from .backends import available_backends, get_backend
from .options import CompileOptions
from .passes import CompileContext, PassPipeline, default_pipeline
from .schedule import SimReport, simulate_schedule


class Compiled:
    """The artifact produced by :func:`compile`.

    Stable surface:
      ``__call__(*args, backend=None)`` — execute via a registered backend
        (default: ``options.backend``).
      ``stream(*args)``   — stream microbatches through the emulated
        systolic pipeline (stream args carry a leading microbatch axis).
      ``simulate(...)``   — discrete-event Fig. 2/5 schedule report.
      ``sweep(...)``      — design-space sweep over memory models × FIFO
        depths × SCC modes (fully simulated grid; ``SweepResult``).
      ``explore(...)``    — partition-space DSE: merge/split/duplicate
        re-partitionings under resource constraints, fully simulated;
        returns a cycles-vs-FIFO-bits Pareto front of ``Compiled``
        artifacts (``DseResult``).
      ``report()``        — per-stage latency / channel summary (text).
      ``cdfg`` / ``partition`` / ``program`` / ``schedule`` — the pass
        products, for inspection and downstream tools.
    """

    def __init__(self, context: CompileContext, pipeline: PassPipeline):
        self.context = context
        self.pipeline = pipeline
        self.fn = context.fn
        self.options = context.options
        #: per-backend runtime state (jitted fns, sharded runners)
        self.runtime_cache: dict[str, Any] = {}

    # -- pass products --------------------------------------------------------

    @property
    def closed_jaxpr(self):
        return self.context.closed_jaxpr

    @property
    def cdfg(self):
        return self.context.cdfg

    @property
    def partition(self):
        return self.context.partition

    @property
    def program(self):
        return self.context.program

    @property
    def schedule(self):
        return self.context.schedule

    @property
    def num_stages(self) -> int:
        return len(self.partition.stages)

    # -- execution ------------------------------------------------------------

    def __call__(self, *args: Any, backend: str | None = None) -> Any:
        return get_backend(backend or self.options.backend).execute(
            self, args)

    def stream(self, *args: Any) -> Any:
        """Run a stream of microbatches through the emulated systolic
        executor; args at ``options.stream_argnums`` have a leading
        microbatch axis, outputs are stacked along it."""
        outs = self.schedule.pipeline.run_emulated(*args)
        return self.unflatten_outputs(list(outs))

    def backends(self) -> tuple[str, ...]:
        """Backends available for this artifact in this environment."""
        return available_backends(self)

    def unflatten_outputs(self, flat: Sequence[Any]) -> Any:
        return jax.tree_util.tree_unflatten(self.context.out_tree,
                                            list(flat))

    # -- analysis -------------------------------------------------------------

    def _serve_defaults(self, kwargs: dict) -> dict:
        """Apply ``options.serve`` (a
        :class:`~repro.dataflow.options.ServeOptions`): default the
        ``server`` argument to its address and install its
        timeout/backoff knobs as the serve-client configuration.  An
        explicit ``server=`` argument still wins."""
        sv = getattr(self.options, "serve", None)
        if sv is not None:
            kwargs.setdefault("server", sv.address or "auto")
            from ..serve import client as _serve_client
            _serve_client.configure_timeouts(sv.timeouts())
        return kwargs

    def simulate(self, n_iters: int = 2048, **kwargs: Any) -> SimReport:
        """Discrete-event simulation of this program on the template vs the
        fused conventional engine (see
        :func:`repro.dataflow.schedule.simulate_schedule`).  Pass
        ``server="auto"`` (or an address) to pre-resolve traces through a
        running resolution daemon — see ``docs/serving.md``."""
        return simulate_schedule(self.schedule, n_iters=n_iters,
                                 **self._serve_defaults(kwargs))

    def sweep(self, **kwargs: Any) -> Any:
        """Design-space sweep: grid the cycle simulator over memory models
        × FIFO depths × ``mem_in_scc`` modes, fully simulated (see
        :func:`repro.dataflow.schedule.sweep_schedule`; dispatched through
        the ``simulate`` backend).  Depth lanes solve deepest-first with
        the depth-incremental warm start, and ``workers=N`` shards the
        trace resolution over the chunk-graph process pool
        (bit-identical; multi-core).  ``server="auto"`` (or an address)
        delegates resolution to a running resolution daemon instead —
        shared pool, cross-client in-flight dedup, streamed chunks;
        results stay bit-identical (``docs/serving.md``)."""
        return get_backend("simulate").sweep(self, **self._serve_defaults(kwargs))

    def explore(self, **kwargs: Any) -> Any:
        """Partition-space DSE (see :func:`repro.dataflow.dse.explore`):
        enumerate legal merge/split/duplicate re-partitionings of this
        kernel, prune against a
        :class:`~repro.dataflow.options.ResourceConstraints` resource
        model, simulate every survivor (sharing resolved traces through
        the chunk-granular per-op rescache), and return a
        :class:`~repro.dataflow.dse.DseResult` whose cycles-vs-FIFO-bits
        Pareto front carries full ``Compiled`` artifacts.  Pass
        ``fifo_depths=[...]`` for the joint partition×FIFO-depth front
        (depth becomes a search axis: every candidate is costed and
        simulated at every depth, one warm-started solve each), and
        ``server="auto"`` to resolve candidate traces through a running
        resolution daemon first (``docs/serving.md``)."""
        from . import dse as _dse
        return _dse.explore(self, **self._serve_defaults(kwargs))

    @property
    def dse_result(self):
        """The ``dse`` pass's exploration (None unless ``options.dse``)."""
        return self.context.dse_result

    @property
    def transform_signature(self) -> str:
        """Active transformation-catalog signature (``"none"`` when the
        pipeline compiled untransformed) — surfaced in :meth:`report`
        and on every sweep row."""
        tf = getattr(self.schedule, "transforms", None)
        return tf.signature() if tf is not None else "none"

    def sim_stages(self, traces: Any = None, **kwargs: Any):
        """Cycle-simulator stage specs (II/latency/mem-in-SCC from the real
        partitioner, traces attached in pipeline order)."""
        return self.schedule.sim_stages(traces, **kwargs)

    def verify(self, fifo_depths: Sequence[int] | None = None,
               *, raise_on_error: bool = False) -> list:
        """Run the static dataflow verifier over this artifact: IR
        invariants (plan/partition/program), the decoupled-access race
        detector, and the FIFO deadlock analysis against
        ``fifo_depths`` (default: the DSE constraints' depth axis, else
        the simulator default of 8).  Returns the
        :class:`~repro.dataflow.verify.Diagnostic` list — empty means
        clean; ``raise_on_error=True`` raises
        :class:`~repro.dataflow.verify.VerifyError` when any
        error-severity finding is present (warnings never raise).  The
        same rules run after every pipeline pass when
        ``options.verify`` is on — see ``docs/verify.md``."""
        from . import verify as _verify
        diags = _verify.verify_compiled(self, fifo_depths)
        if raise_on_error and any(d.severity == "error" for d in diags):
            raise _verify.VerifyError(diags, where="verify()")
        return diags

    def report(self) -> str:
        """Per-stage latency / channel summary."""
        sch = self.schedule
        opts = self.options
        lines = [
            f"dataflow program: {len(self.cdfg.nodes)} ops -> "
            f"{sch.num_stages} stages, {sch.num_channels} channels "
            f"({sch.channel_bytes}B/token), policy={opts.policy!r}, "
            f"backend={opts.backend!r}",
            f"  pipeline II={sch.pipeline_ii}  "
            f"total latency={sch.total_latency}  "
            f"bubble@8mb={sch.bubble_fraction(8):.2f}",
            f"  passes: {' -> '.join(self.pipeline.names())}  "
            f"transforms: {self.transform_signature}",
        ]
        for s in sch.stages:
            tags = [t for t, on in (("MEM", s.has_memory),
                                    ("LONG", s.has_long),
                                    ("MEM-IN-SCC", s.mem_in_scc)) if on]
            prims = ",".join(s.prims[:6]) + ("…" if len(s.prims) > 6 else "")
            lines.append(
                f"  stage {s.id}: [{prims}] ii={s.ii} lat={s.latency} "
                f"in={s.in_channel_bytes}B out={s.out_channel_bytes}B "
                f"{'|'.join(tags)}"
                + (f" regions={list(s.regions)}" if s.regions else ""))
        for name, dt in self.context.timings.items():
            lines.append(f"  pass {name:<10} {dt * 1e3:8.2f} ms")
        diags = self.verify()
        errs = sum(d.severity == "error" for d in diags)
        warns = len(diags) - errs
        lines.append(
            "  verify: clean" if not diags else
            f"  verify: {errs} error(s), {warns} warning(s)")
        for d in diags[:4]:
            lines.append(f"    {d}")
        return "\n".join(lines)

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return (f"<Compiled {getattr(self.fn, '__name__', '?')} "
                f"stages={self.num_stages} backend={self.options.backend}>")


# ---------------------------------------------------------------------------
# Compilation cache
# ---------------------------------------------------------------------------

_CACHE: dict[tuple, Compiled] = {}
_STATS = {"hits": 0, "misses": 0}


def _cache_key(closed_jaxpr: Any, out_tree: Any, options: CompileOptions,
               pipeline: PassPipeline) -> tuple:
    # Consts are keyed by identity: make_jaxpr closes over the *same* array
    # objects on retrace, and the cached Compiled keeps them alive, so ids
    # are stable exactly as long as the entry exists.  out_tree
    # disambiguates functions whose flat computation is identical but whose
    # return container differs.
    return (
        str(closed_jaxpr.jaxpr),
        tuple(str(v.aval) for v in closed_jaxpr.jaxpr.invars),
        tuple(id(c) for c in closed_jaxpr.consts),
        out_tree,
        options,
        pipeline.signature(),
    )


def clear_cache() -> None:
    _CACHE.clear()
    _STATS["hits"] = _STATS["misses"] = 0


def cache_stats() -> dict[str, int]:
    return {"size": len(_CACHE), **_STATS}


# ---------------------------------------------------------------------------
# Entry points
# ---------------------------------------------------------------------------


def compile(  # noqa: A001 - deliberate: repro.dataflow.compile
    fn: Callable,
    *example_args: Any,
    options: CompileOptions | None = None,
    pipeline: PassPipeline | None = None,
    use_cache: bool = True,
    **option_kwargs: Any,
) -> Compiled:
    """Compile ``fn`` for the dataflow template and return a
    :class:`Compiled` artifact.

    ``example_args`` may be concrete arrays or ``jax.ShapeDtypeStruct``
    trees (analysis-only use).  Options come either as a
    :class:`CompileOptions` or as keyword shorthands
    (``compile(fn, x, policy="fused")``).
    """
    if options is None:
        options = CompileOptions(**option_kwargs)
    elif option_kwargs:
        options = options.replace(**option_kwargs)
    pipeline = pipeline or default_pipeline()

    ctx = CompileContext(fn=fn, example_args=example_args, options=options)
    # run the front end first: the cache key needs the jaxpr
    pipeline.run(ctx, stop=1)
    key = None
    if use_cache and ctx.closed_jaxpr is not None:
        key = _cache_key(ctx.closed_jaxpr, ctx.out_tree, options, pipeline)
        hit = _CACHE.get(key)
        if hit is not None:
            _STATS["hits"] += 1
            return hit
        _STATS["misses"] += 1
    pipeline.run(ctx, start=1)
    compiled = Compiled(ctx, pipeline)
    if key is not None:
        _CACHE[key] = compiled
    return compiled


def _abstract_key(args: tuple) -> tuple:
    flat, treedef = jax.tree_util.tree_flatten(args)
    return treedef, tuple(
        (tuple(np.shape(x)), str(jnp.result_type(x))) for x in flat)


def dataflow_jit(
    fn: Callable | None = None,
    *,
    options: CompileOptions | None = None,
    pipeline: PassPipeline | None = None,
    **option_kwargs: Any,
) -> Callable:
    """Decorator form of :func:`compile`: traces lazily on first call (per
    argument-shape signature) and dispatches to the selected backend.

    ::

        @dataflow_jit(stream_argnums=(1,))
        def kernel(table, idx, w): ...

        kernel(table, idx, w)                      # options.backend
        kernel(table, idx, w, backend="emulated")  # explicit dispatch
        kernel.lower(table, idx, w).report()       # the Compiled artifact

    Keyword arguments to the wrapped function are bound to positional form
    via its signature (``backend`` is reserved for dispatch — pass a
    same-named function parameter positionally).
    """
    if options is None:
        opts = CompileOptions(**option_kwargs)
    elif option_kwargs:
        opts = options.replace(**option_kwargs)
    else:
        opts = options

    def wrap(f: Callable) -> Callable:
        by_shape: dict[tuple, Compiled] = {}
        state: dict[str, Any] = {}

        def bind(args: tuple, kwargs: dict) -> tuple:
            if not kwargs:
                return args
            if "sig" not in state:
                state["sig"] = inspect.signature(f)
            return state["sig"].bind(*args, **kwargs).args

        def lower(*args: Any, **kwargs: Any) -> Compiled:
            args = bind(args, kwargs)
            with trace.span("dataflow.lower"):
                key = _abstract_key(args)
                compiled = by_shape.get(key)
                if compiled is None:
                    compiled = compile(f, *args, options=opts,
                                       pipeline=pipeline)
                    by_shape[key] = compiled
            return compiled

        def wrapper(*args: Any, backend: str | None = None,
                    **kwargs: Any) -> Any:
            args = bind(args, kwargs)
            return lower(*args)(*args, backend=backend)

        wrapper.__name__ = getattr(f, "__name__", "dataflow_jit")
        wrapper.__doc__ = getattr(f, "__doc__", None)
        wrapper.__wrapped__ = f
        wrapper.lower = lower
        wrapper.options = opts
        return wrapper

    return wrap(fn) if fn is not None else wrap
