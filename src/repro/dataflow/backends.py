"""Pluggable execution backends for compiled dataflow programs.

A backend turns a :class:`~repro.dataflow.driver.Compiled` artifact plus
call arguments into results.  The registry maps names to backend objects;
``Compiled.__call__(... , backend="name")`` dispatches here.  Registering
a new backend is one call::

    @register_backend
    class MyBackend(Backend):
        name = "mine"
        def execute(self, compiled, args): ...

Built-ins:

* ``sequential`` — replay the decoupled stages in topological order
  (bit-exact oracle for the pipelined executors).
* ``emulated``   — the tick/ppermute systolic schedule in Python on one
  device (schedule-exact, used for tests and CPU demos).
* ``systolic``   — the shard_map executor: one pipeline stage per device
  along a ``stage`` mesh axis (needs ``num_stages`` devices).
* ``xla``        — ``jax.jit`` of the original fused function: the
  conventional-accelerator baseline, and the production serving path.
* ``simulate``   — the discrete-event machine model; returns a
  :class:`~repro.dataflow.schedule.SimReport` instead of outputs.
"""

from __future__ import annotations

from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..core.decouple import run_stages_sequential


class BackendUnavailableError(RuntimeError):
    """Raised when a backend cannot run in the current environment."""


class Backend:
    """Base class: subclasses set ``name`` and implement ``execute``."""

    name: str = "?"
    kind: str = "execute"  # "execute" backends return fn's outputs

    def is_available(self, compiled: Any) -> bool:
        return True

    def execute(self, compiled: Any, args: Sequence[Any]) -> Any:
        raise NotImplementedError

    def __repr__(self) -> str:  # pragma: no cover - debug aid
        return f"<backend {self.name!r} ({self.kind})>"


_REGISTRY: dict[str, Backend] = {}


def register_backend(backend: Any = None, *, overwrite: bool = False) -> Any:
    """Register a backend instance or class (instantiated with no args).
    Usable as a decorator."""
    if backend is None:
        return lambda b: register_backend(b, overwrite=overwrite)
    inst = backend() if isinstance(backend, type) else backend
    if inst.name in _REGISTRY and not overwrite:
        raise ValueError(f"backend {inst.name!r} already registered")
    _REGISTRY[inst.name] = inst
    return backend


def unregister_backend(name: str) -> None:
    _REGISTRY.pop(name, None)


def get_backend(name: str) -> Backend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown backend {name!r}; registered: "
            f"{sorted(_REGISTRY)}") from None


def registered_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def execute_backends() -> tuple[str, ...]:
    """Names of backends that produce the function's outputs."""
    return tuple(sorted(n for n, b in _REGISTRY.items()
                        if b.kind == "execute"))


def available_backends(compiled: Any) -> tuple[str, ...]:
    return tuple(sorted(n for n, b in _REGISTRY.items()
                        if b.is_available(compiled)))


# ---------------------------------------------------------------------------
# Built-ins
# ---------------------------------------------------------------------------


def _expand_stream_args(compiled: Any, args: Sequence[Any]) -> list[Any]:
    """Single-shot call → one-microbatch stream: stream args gain a
    leading axis of 1."""
    args = list(args)
    for i in compiled.options.stream_argnums:
        if i < len(args):
            args[i] = jax.tree_util.tree_map(
                lambda a: jnp.asarray(a)[None], args[i])
    return args


@register_backend
class SequentialBackend(Backend):
    name = "sequential"

    def execute(self, compiled: Any, args: Sequence[Any]) -> Any:
        outs = run_stages_sequential(compiled.program, *args)
        return compiled.unflatten_outputs(outs)


@register_backend
class EmulatedBackend(Backend):
    name = "emulated"

    def execute(self, compiled: Any, args: Sequence[Any]) -> Any:
        outs = compiled.schedule.pipeline.run_emulated(
            *_expand_stream_args(compiled, args))
        return compiled.unflatten_outputs([o[0] for o in outs])


@register_backend
class SystolicBackend(Backend):
    """shard_map executor: stage *s* on device *s*; needs one device per
    pipeline stage."""

    name = "systolic"

    def is_available(self, compiled: Any) -> bool:
        return len(jax.devices()) >= compiled.num_stages

    def _runner(self, compiled: Any):
        cached = compiled.runtime_cache.get(self.name)
        if cached is not None:
            return cached
        S = compiled.num_stages
        devices = jax.devices()
        if len(devices) < S:
            raise BackendUnavailableError(
                f"systolic backend needs {S} devices (one per stage), "
                f"have {len(devices)}; set "
                f"--xla_force_host_platform_device_count or use the "
                f"'emulated' backend")
        mesh = Mesh(np.asarray(devices[:S]), ("stage",))
        run = compiled.schedule.pipeline.build_sharded(mesh)
        compiled.runtime_cache[self.name] = run
        return run

    def execute(self, compiled: Any, args: Sequence[Any]) -> Any:
        run = self._runner(compiled)
        outs = run(*_expand_stream_args(compiled, args))
        return compiled.unflatten_outputs([o[0] for o in outs])


@register_backend
class XLABackend(Backend):
    """The fused baseline: hand the whole function to XLA unchanged.  This
    is the production path when the program should run as one kernel —
    the driver still yields the partition/schedule analysis around it.
    Arguments at ``options.donate_argnums`` are donated to the call."""

    name = "xla"

    def execute(self, compiled: Any, args: Sequence[Any]) -> Any:
        jitted = compiled.runtime_cache.get(self.name)
        if jitted is None:
            jitted = jax.jit(compiled.fn,
                             donate_argnums=compiled.options.donate_argnums)
            compiled.runtime_cache[self.name] = jitted
        return jitted(*args)


@register_backend
class SimulateBackend(Backend):
    """Discrete-event machine model (Fig. 2/5); ignores call arguments and
    returns a SimReport.  Also hosts the design-space sweep
    (``Compiled.sweep`` dispatches here), so an alternative simulation
    backend can override both entry points together."""

    name = "simulate"
    kind = "analyze"

    def execute(self, compiled: Any, args: Sequence[Any]) -> Any:
        del args
        return compiled.simulate()

    def sweep(self, compiled: Any, **kwargs: Any) -> Any:
        from .schedule import sweep_schedule
        return sweep_schedule(compiled.schedule, **kwargs)
