"""Dataflow pipeline executors — the template realized on a device mesh.

Two executors, mirroring the two ways the paper's template shows up on TPU:

* :class:`SystolicPipeline` (heterogeneous stages).  Runs a
  :class:`~repro.core.decouple.DecoupledProgram` over a ``stage`` mesh axis:
  device *s* executes pipeline stage *s*; channel payloads move one hop per
  tick via ``lax.ppermute`` (the ICI link is the FIFO wire, the per-device
  word buffer is the FIFO storage).  Microbatch *m* occupies stage *s* at
  tick ``t = m + s`` — exactly the paper's Fig. 2 schedule, where a stall in
  one stage does not halt the others.

* :func:`pipeline_apply` (homogeneous stages — classic pipeline parallelism).
  One stage function, per-stage parameters sharded over the ``stage`` axis;
  GPipe-style fill/drain schedule with ``M`` microbatches (bubble fraction
  ``(S-1)/(M+S-1)``).  Differentiable: ``jax.grad`` flows through the
  ``ppermute``s, so the same executor trains (GPipe) and serves.

Both have a pure-Python *emulated* mode used by unit tests on a single
device; the shard_map path is exercised by the multi-device subprocess tests
and by the dry-run.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import AxisType, Mesh, PartitionSpec as P

from .channels import ChannelSpec
from .decouple import DecoupledProgram


def _auto_axes(mesh: Mesh) -> Mesh:
    """The same devices and axis names with ``Auto`` axis types.

    The executors are written in shard_map's per-device style and leave
    placement outside the map to XLA.  Meshes from ``jax.make_mesh``
    default to ``Explicit`` axes, whose sharding types would ride on the
    replicated result and make ``jax.grad`` of a loss over it fail
    outside a ``jax.set_mesh`` context."""
    return mesh.update(axis_types=(AxisType.Auto,) * len(mesh.axis_names))


# ---------------------------------------------------------------------------
# Heterogeneous systolic executor over a DecoupledProgram
# ---------------------------------------------------------------------------

def _example_for_var(v: Any) -> jax.Array:
    """Zero example matching the runtime value of a boundary var.

    Must agree exactly with what :class:`ChannelSpec` will see at run time:
    zero-rank avals keep their ``()`` shape, and the dtype is canonicalized
    (e.g. f64 → f32 under disabled x64) so the packed word width of the
    boundary spec matches the packed width of the live payload.
    """
    aval = getattr(v, "aval", None)
    shape = tuple(getattr(aval, "shape", ()) or ())
    dtype = getattr(aval, "dtype", None)
    if dtype is None:
        dtype = jnp.float32
    dtype = jax.dtypes.canonicalize_dtype(np.dtype(dtype))
    return jnp.zeros(shape, dtype)


@dataclasses.dataclass
class _BoundarySpec:
    vars: list[Any]
    spec: ChannelSpec


class SystolicPipeline:
    """Execute a decoupled program as a systolic pipeline over microbatches.

    Channels between non-adjacent stages are linearized: boundary *b* carries
    every var produced by stages ``<= b`` and still needed by stages ``> b``
    (intermediate stages forward them).  All boundaries are padded to one
    transport width so a single ``ppermute`` word per tick suffices.

    ``stream_argnums`` are the positions of the original function's arguments
    that vary per microbatch (leading axis = microbatch); the remaining
    arguments are per-stage constants (weights), available to every stage.
    """

    def __init__(self, prog: DecoupledProgram,
                 stream_argnums: Sequence[int] = (0,)):
        self.prog = prog
        self.stream_argnums = tuple(stream_argnums)
        self.num_stages = len(prog.stages)
        self._build_boundaries()

    # -- static analysis ----------------------------------------------------

    def _build_boundaries(self) -> None:
        prog = self.prog
        S = self.num_stages
        produced_at: dict[Any, int] = {}
        for sp in prog.stages:
            for v in sp.out_vars:
                produced_at[v] = sp.stage_id
        needed_from: dict[Any, int] = {}
        for sp in prog.stages:
            for (tag, ref), v in zip(sp.in_from, sp.in_vars):
                if tag == "chan":
                    needed_from[v] = max(needed_from.get(v, -1), sp.stage_id)
        # final outputs must survive to the last boundary
        for tag, ref in prog.out_sources:
            if tag == "chan":
                needed_from[ref] = max(needed_from.get(ref, -1), S - 1)

        self.boundaries: list[_BoundarySpec] = []
        for b in range(S):  # boundary b sits after stage b
            vars_b = [v for v, p in produced_at.items()
                      if p <= b and needed_from.get(v, -1) > b
                      or (p <= b and b == S - 1 and any(
                          t == "chan" and r is v
                          for t, r in prog.out_sources))]
            # deterministic order
            vars_b = sorted(set(vars_b), key=lambda v: (produced_at[v],
                                                        str(v)))
            example = tuple(_example_for_var(v) for v in vars_b)
            self.boundaries.append(
                _BoundarySpec(vars_b, ChannelSpec.from_example(example)))
        self.width = max([1] + [b.spec.width for b in self.boundaries])

    # -- per-stage wrapped function ------------------------------------------

    def _stage_fn(self, s: int) -> Callable:
        prog = self.prog
        sp = prog.stages[s]
        in_spec = self.boundaries[s - 1] if s > 0 else None
        out_spec = self.boundaries[s]
        consts = prog.partition.cdfg.closed_jaxpr.consts

        def fn(word_in: jax.Array, stream_args: tuple,
               const_args: dict[int, Any]):
            env: dict[Any, Any] = {}
            if in_spec is not None and in_spec.vars:
                payload = in_spec.spec.unpack(word_in[:in_spec.spec.width])
                for v, val in zip(in_spec.vars, payload):
                    env[v] = val
            args_map: dict[int, Any] = {}
            for i, a in zip(self.stream_argnums, stream_args):
                args_map[i] = a
            ins = []
            for (tag, ref), v in zip(sp.in_from, sp.in_vars):
                if tag == "arg":
                    ins.append(args_map[ref] if ref in args_map
                               else const_args[ref])
                elif tag == "const":
                    ins.append(consts[ref])
                else:
                    ins.append(env[v])
            outs = sp.fn(*ins)
            for v, o in zip(sp.out_vars, outs):
                env[v] = o
            payload_out = tuple(env[v] for v in out_spec.vars)
            word_out = out_spec.spec.pack(payload_out, pad_to=self.width)
            if s == self.num_stages - 1:
                res = []
                for tag, ref in prog.out_sources:
                    if tag == "chan":
                        res.append(env[ref])
                    elif tag == "arg":
                        res.append(args_map[ref] if ref in args_map
                                   else const_args[ref])
                    elif tag == "const":
                        res.append(consts[ref])
                    else:
                        res.append(jnp.asarray(ref))
                y = tuple(res)
            else:
                y = None
            return word_out, y

        return fn

    # -- emulated execution (single device, schedule-exact) -------------------

    def run_emulated(self, *args: Any) -> tuple:
        """Run the exact tick/ppermute schedule in Python (one device).

        Produces the same numerics as the shard_map executor and the same
        per-tick occupancy; used for schedule unit tests and CPU demos.
        """
        S = self.num_stages
        stream = [args[i] for i in self.stream_argnums]
        T = int(jax.tree_util.tree_leaves(stream[0])[0].shape[0])
        const_args = {j: a for j, a in enumerate(args)
                      if j not in self.stream_argnums}
        fns = [self._stage_fn(s) for s in range(S)]

        words = [jnp.zeros((self.width,), jnp.uint32) for _ in range(S)]
        outputs: list[Any] = [None] * T
        for t in range(T + S - 1):
            new_words = list(words)
            for s in range(S):
                m = t - s
                if not (0 <= m < T):
                    continue
                xs = tuple(jax.tree_util.tree_map(lambda a: a[m], x)
                           for x in stream)
                word_in = words[s - 1] if s > 0 else jnp.zeros(
                    (self.width,), jnp.uint32)
                w_out, y = fns[s](word_in, xs, const_args)
                new_words[s] = w_out
                if s == S - 1:
                    outputs[m] = y
            # ppermute: boundary words shift one stage per tick.  We emulate
            # by double-buffering: stage s+1 at tick t+1 reads stage s's
            # output from tick t.
            words = new_words
        outs = jax.tree_util.tree_map(lambda *xs: jnp.stack(xs), *outputs)
        return outs

    # -- shard_map execution ---------------------------------------------------

    def build_sharded(self, mesh: Mesh, axis: str = "stage") -> Callable:
        """Return ``run(*args) -> stacked outputs`` executing on ``mesh``
        with one pipeline stage per device along ``axis``."""
        S = self.num_stages
        if mesh.shape[axis] != S:
            raise ValueError(
                f"mesh axis {axis!r} has size {mesh.shape[axis]}, "
                f"need {S} (one device per stage)")
        fns = [self._stage_fn(s) for s in range(S)]

        def run(*args: Any):
            stream = [args[i] for i in self.stream_argnums]
            T = int(jax.tree_util.tree_leaves(stream[0])[0].shape[0])
            const_args = {j: a for j, a in enumerate(args)
                          if j not in self.stream_argnums}

            # probe output structure once (stage S-1 on microbatch 0)
            xs0 = tuple(jax.tree_util.tree_map(lambda a: a[0], x)
                        for x in stream)
            _, y0 = jax.eval_shape(
                lambda w, xs, ca: fns[S - 1](w, xs, ca),
                jax.ShapeDtypeStruct((self.width,), jnp.uint32),
                xs0, const_args)

            def per_device(stream_dev, *const_flat):
                const_args_dev = jax.tree_util.tree_unflatten(
                    const_treedef, const_flat)
                sidx = jax.lax.axis_index(axis)

                def tick(carry, t):
                    word, out_buf = carry
                    m = t - sidx
                    valid = (m >= 0) & (m < T)
                    m_c = jnp.clip(m, 0, T - 1)
                    xs = tuple(jax.tree_util.tree_map(
                        lambda a: jax.lax.dynamic_index_in_dim(
                            a, m_c, 0, keepdims=False), x)
                        for x in stream_dev)

                    branches = []
                    for s in range(S):
                        def mk(s):
                            def br(w, xs_):
                                w_out, y = fns[s](w, xs_, const_args_dev)
                                if y is None:
                                    y = jax.tree_util.tree_map(
                                        lambda sd: jnp.zeros(sd.shape,
                                                             sd.dtype), y0)
                                return w_out, y
                            return br
                        branches.append(mk(s))
                    w_out, y = jax.lax.switch(sidx, branches, word, xs)

                    write = valid & (sidx == S - 1)
                    out_buf = jax.tree_util.tree_map(
                        lambda buf, yv: jnp.where(
                            write,
                            jax.lax.dynamic_update_index_in_dim(
                                buf, yv, m_c, 0),
                            buf),
                        out_buf, y)
                    w_next = jax.lax.ppermute(
                        w_out, axis,
                        [(i, (i + 1) % S) for i in range(S)])
                    return (w_next, out_buf), None

                out_buf0 = jax.tree_util.tree_map(
                    lambda sd: jnp.zeros((T,) + sd.shape, sd.dtype), y0)
                word0 = jnp.zeros((self.width,), jnp.uint32)
                (_, out_buf), _ = jax.lax.scan(
                    tick, (word0, out_buf0), jnp.arange(T + S - 1))
                # every device returns a buffer; only stage S-1's is real.
                # psum the masked buffers so the result is replicated.
                out_buf = jax.tree_util.tree_map(
                    lambda b: jax.lax.psum(
                        jnp.where(sidx == S - 1, b,
                                  jnp.zeros_like(b)), axis),
                    out_buf)
                return out_buf

            const_flat, const_treedef = jax.tree_util.tree_flatten(const_args)
            shard = jax.shard_map(
                per_device, mesh=_auto_axes(mesh),
                in_specs=(P(),) * (1 + len(const_flat)),
                out_specs=P(), check_vma=False)
            return shard(tuple(stream), *const_flat)

        return run


# ---------------------------------------------------------------------------
# Homogeneous pipeline parallelism (classic PP with the template's channels)
# ---------------------------------------------------------------------------

def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "stage",
) -> jax.Array:
    """GPipe-style forward over ``S = mesh.shape[axis]`` stages.

    ``stage_params`` leaves have leading dim ``S`` (sharded over ``axis``);
    ``microbatches`` has shape ``(M, ...)`` (replicated).  Returns ``(M, ...)``
    outputs (replicated).  Differentiable — ``jax.grad`` through the
    ``ppermute`` gives the reverse pipeline automatically.
    """
    S = mesh.shape[axis]
    M = microbatches.shape[0]

    def per_device(params_blk, mb):
        params_s = jax.tree_util.tree_map(lambda p: p[0], params_blk)
        sidx = jax.lax.axis_index(axis)

        def tick(carry, t):
            act_in, out_buf = carry
            m = t - sidx
            valid = (m >= 0) & (m < M)
            m_c = jnp.clip(m, 0, M - 1)
            x0 = jax.lax.dynamic_index_in_dim(mb, m_c, 0, keepdims=False)
            x = jnp.where(sidx == 0, x0, act_in)
            y = stage_fn(params_s, x)
            y = jnp.where(valid, y, jnp.zeros_like(y))
            out_buf = jnp.where(
                valid & (sidx == S - 1),
                jax.lax.dynamic_update_index_in_dim(out_buf, y, m_c, 0),
                out_buf)
            act_next = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % S) for i in range(S)])
            return (act_next, out_buf), None

        zero_act = jnp.zeros(mb.shape[1:], mb.dtype)
        out0 = jnp.zeros_like(mb)
        (_, out_buf), _ = jax.lax.scan(
            tick, (zero_act, out0), jnp.arange(M + S - 1))
        out_buf = jax.lax.psum(
            jnp.where(sidx == S - 1, out_buf, jnp.zeros_like(out_buf)),
            axis)
        return out_buf

    return jax.shard_map(
        per_device, mesh=_auto_axes(mesh),
        in_specs=(P(axis), P()),
        out_specs=P(), check_vma=False,
    )(stage_params, microbatches)


def pipeline_apply_emulated(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    num_stages: int,
) -> jax.Array:
    """Schedule-exact single-device emulation of :func:`pipeline_apply`."""
    S = num_stages
    M = microbatches.shape[0]
    acts = [jnp.zeros(microbatches.shape[1:], microbatches.dtype)
            for _ in range(S)]
    outs = [None] * M
    for t in range(M + S - 1):
        new_acts = list(acts)
        for s in range(S):
            m = t - s
            if not (0 <= m < M):
                continue
            x = microbatches[m] if s == 0 else acts[s - 1]
            p = jax.tree_util.tree_map(lambda q: q[s], stage_params)
            y = stage_fn(p, x)
            new_acts[s] = y
            if s == S - 1:
                outs[m] = y
        acts = new_acts
    return jnp.stack(outs)


def gpipe_bubble_fraction(num_stages: int, num_microbatches: int) -> float:
    """Fill/drain overhead of the schedule (paper Fig. 2's ramp)."""
    return (num_stages - 1) / (num_microbatches + num_stages - 1)
