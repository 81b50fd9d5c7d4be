"""Serving drivers: the resolution daemon CLI and the LM batch demo.

Resolution daemon (the serving tier of the simulation stack — see
:mod:`repro.serve` and ``docs/serving.md``):

    PYTHONPATH=src python -m repro.launch.serve daemon \
        --store-dir ~/.cache/repro-rescache
    PYTHONPATH=src python -m repro.launch.serve stats      # JSON
    PYTHONPATH=src python -m repro.launch.serve shutdown

LM serving demo (CPU-scale, reduced config) — batched prefill + decode
with a continuous batch queue:

    PYTHONPATH=src python -m repro.launch.serve \
        --arch smollm-135m --reduced --requests 4 --gen 16

The demo is the template end-to-end: request admission is a bounded
FIFO (HostFIFO), prefill is the burst-access stage, the KV cache is the
customized memory partition, and decode steps stream it back.  The
heavy imports (jax, the model zoo) are deferred so the daemon
subcommands start without them.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import logging
import sys
import time

import numpy as np

from .. import trace

log = logging.getLogger("repro.serve")


@dataclasses.dataclass
class Request:
    id: int
    prompt: np.ndarray          # (S,) int32
    max_new_tokens: int


@dataclasses.dataclass
class Result:
    id: int
    tokens: list
    prefill_s: float
    decode_s: float


class BatchedServer:
    """Static-batch server: groups requests, prefills once, decodes in
    lockstep (continuous batching is a straightforward extension — slots
    re-admit on completion; kept static for deterministic tests).

    ``serve`` records its phases in :mod:`repro.trace`: spans
    ``serve.prefill``, ``serve.decode`` and, per decode step,
    ``serve.sync`` (waiting for the last token) and ``serve.dispatch``
    (enqueueing the step and its argmax), each with the batch's first
    request id as ``batch``; counters ``serve.batches``,
    ``serve.decode_steps``, ``serve.tokens_decoded`` (batch × steps),
    ``serve.tokens_returned`` and ``serve.cache_donated`` (decode steps
    whose incoming KV cache the step consumed in place).  A model with
    expert layers also counts, over its prefills and decode steps,
    ``moe.pairs_routed`` (tokens × top-k × expert layers),
    ``moe.pairs_held`` (the pairs routed to an expert this model holds)
    and ``moe.pairs_max_expert`` (the sum over layer-steps of the busiest
    held expert's pairs); each step yields its pairs per layer and held
    expert, read with the token the loop already waits for.  It also
    counts ``moe.grouped.pallas`` and ``moe.grouped.ragged_dot``: the
    expert-layer dispatches (per prefill block and per decode step) that
    ran on each path of the grouped matmul, the path the model takes on
    this backend (:func:`repro.models.moe.grouped_path`).  A model with
    absorbed-MLA layers counts ``attn.mla_decode.pallas`` and
    ``attn.mla_decode.xla``, its MLA layer-steps of decode on each path of
    the latent attention (:func:`repro.models.attention.mla_decode_path`),
    and ``attn.mla_decode.blocks_live`` / ``.blocks_total``, the
    128-position blocks of the rows' latent caches those layer-steps read
    and the blocks the caches hold.

    The decode step donates its cache argument: each step writes this
    token's entries into the cache it was given, so the cache a caller
    passes in (the prefill's, then each step's) is consumed."""

    def __init__(self, cfg, params, *, max_len: int = 256):
        from ..dataflow import dataflow_jit
        from ..models import decode_step as _decode, prefill as _prefill
        self.cfg = cfg
        self.params = params
        self.max_len = max_len
        self._prefill_fn = _prefill
        self._decode_fn = _decode
        #: expert layers, whose steps also yield their routed pairs
        self._moe_layers = sum(seg.repeats * sum(s.mlp == "moe"
                                                 for s in seg.unit)
                               for seg in cfg.segments)
        load = {"expert_load": True} if self._moe_layers else {}
        #: absorbed-MLA layers, whose decode attention has two paths
        self._mla_layers = sum(
            seg.repeats * sum(s.mixer == "mla" for s in seg.unit)
            for seg in cfg.segments) if cfg.mla_absorbed else 0

        # named for the trace's modules: jit_prefill_step, jit_decode_step
        def prefill_step(p, t):
            return _prefill(p, t, cfg, max_len, **load)

        def decode_step(p, tok, cache, ln):
            return _decode(p, tok, cache, ln, cfg, **load)

        # Both steps go through the dataflow compiler driver.  The "xla"
        # backend executes exactly as jax.jit did, but the Compiled
        # artifact (`.lower(...)`) exposes the Algorithm-1 stage/channel
        # analysis of the serving steps — see dataflow_report().  The
        # decode step donates the cache (argument 2), so XLA updates it in
        # place instead of copying it into a new buffer every token.
        self._prefill = dataflow_jit(prefill_step, backend="xla")
        self._decode = dataflow_jit(decode_step, backend="xla",
                                    donate_argnums=(2,))

    def dataflow_report(self, requests: list["Request"]) -> str:
        """Stage/channel report of the decode step for this batch shape."""
        import jax
        import jax.numpy as jnp
        B = len(requests)
        tok = jnp.zeros((B,), jnp.int32)
        _, cache = jax.eval_shape(
            lambda p, t: self._prefill_fn(p, t, self.cfg, self.max_len),
            self.params, jax.ShapeDtypeStruct((B, 8), jnp.int32))
        compiled = self._decode.lower(self.params, tok, cache,
                                      jnp.asarray(8, jnp.int32))
        return compiled.report()

    def prefill(self, requests: list[Request]):
        """Batched prompt forward: ``(last-position logits, KV cache)``,
        and for a model with expert layers their pairs per held expert.
        Prompts are left-aligned and right-padded with zeros (masked by
        position)."""
        import jax.numpy as jnp
        S = max(len(r.prompt) for r in requests)
        prompts = np.zeros((len(requests), S), np.int32)
        for i, r in enumerate(requests):
            prompts[i, :len(r.prompt)] = r.prompt
        return self._prefill(self.params, jnp.asarray(prompts))

    def serve(self, requests: list[Request]) -> list[Result]:
        import jax
        import jax.numpy as jnp
        S = max(len(r.prompt) for r in requests)
        batch = requests[0].id
        moe = self._moe_layers > 0
        with trace.span("serve.prefill", batch=batch) as prefill:
            logits, cache, *load = self.prefill(requests)
            logits = jax.block_until_ready(logits)
        loads = [(len(requests) * S, np.asarray(load[0]))] if moe else []

        gen = max(r.max_new_tokens for r in requests)
        tokens = []
        tok = jnp.argmax(logits, -1)
        with trace.span("serve.decode", batch=batch) as decode_span:
            length = jnp.asarray(S, jnp.int32)
            # lower once: shapes are fixed after prefill, so the decode
            # loop calls the Compiled artifact directly instead of
            # re-keying the params+cache pytree every token.  Lowered with
            # shapes, so the artifact keeps no cache that the first step
            # consumes.
            decode = self._decode.lower(*jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                (self.params, tok.astype(jnp.int32), cache, length)))
            donated = 0
            for step in range(gen):
                with trace.span("serve.sync", batch=batch):
                    if step and moe:
                        t, lo = jax.device_get((tok, load[0]))
                        tokens.append(t)
                        loads.append((len(requests), lo))
                    else:
                        tokens.append(np.asarray(tok))
                with trace.span("serve.dispatch", batch=batch):
                    given = jax.tree_util.tree_leaves(cache)[0]
                    logits, cache, *load = decode(
                        self.params, tok.astype(jnp.int32), cache,
                        length + step)
                    tok = jnp.argmax(logits, -1)
                donated += given.is_deleted()
            jax.block_until_ready(logits)
            if moe:
                loads.append((len(requests), np.asarray(load[0])))

        outs = []
        seq = np.stack(tokens, 1)  # (B, gen)
        for i, r in enumerate(requests):
            outs.append(Result(r.id, seq[i, :r.max_new_tokens].tolist(),
                               prefill.seconds, decode_span.seconds / gen))
        trace.count("serve.batches")
        trace.count("serve.decode_steps", gen)
        trace.count("serve.tokens_decoded", len(requests) * gen)
        trace.count("serve.tokens_returned",
                    sum(len(o.tokens) for o in outs))
        trace.count("serve.cache_donated", donated)
        if moe:
            self._count_pairs(loads)
            self._count_dispatches(len(requests), S, gen)
        if self._mla_layers:
            self._count_mla_decode(len(requests), S, gen)
        return outs

    def _count_mla_decode(self, batch: int, prompt: int, gen: int) -> None:
        """``attn.mla_decode.<path>``: a batch's absorbed-MLA layer-steps
        on the path the decode step was traced with, the other path 0;
        ``attn.mla_decode.blocks_live`` and ``.blocks_total``: the
        128-position blocks of the rows' latent caches that those
        layer-steps read, and the blocks the caches hold."""
        from ..models import attention
        path = attention.mla_decode_path()
        for p in ("pallas", "xla"):
            trace.count(f"attn.mla_decode.{p}",
                        self._mla_layers * gen if p == path else 0)
        blocks = [attention.mla_decode_blocks(prompt + step, self.max_len)
                  for step in range(gen)]
        rows = self._mla_layers * batch
        trace.count("attn.mla_decode.blocks_live",
                    rows * sum(n for n, _ in blocks))
        trace.count("attn.mla_decode.blocks_total",
                    rows * sum(n for _, n in blocks))

    def _count_dispatches(self, batch: int, prompt: int, gen: int) -> None:
        """``moe.grouped.<path>``: a batch's expert-layer dispatches, its
        prefill's blocks and one a decode step, on the path the steps were
        traced with; the other path counts 0."""
        from ..models import moe
        n = self._moe_layers * (moe.grouped_blocks(batch * prompt)
                                + gen * moe.grouped_blocks(batch))
        path = moe.grouped_path()
        for p in ("pallas", "ragged_dot"):
            trace.count(f"moe.grouped.{p}", n if p == path else 0)

    def _count_pairs(self, loads: list[tuple[int, np.ndarray]]) -> None:
        """The ``moe.*`` counters of a batch's steps: ``(tokens, pairs per
        expert layer and held expert)`` for each."""
        k = self.cfg.moe.top_k
        trace.count("moe.pairs_routed",
                    sum(t for t, _ in loads) * k * self._moe_layers)
        trace.count("moe.pairs_held", sum(int(a.sum()) for _, a in loads))
        trace.count("moe.pairs_max_expert",
                    sum(int(a.max(axis=1).sum()) for _, a in loads))


# ---------------------------------------------------------------------------
# Resolution daemon CLI
# ---------------------------------------------------------------------------

def _serve_cli(argv: list[str]) -> int:
    p = argparse.ArgumentParser(
        prog="python -m repro.launch.serve",
        description="resolution daemon control (see docs/serving.md)")
    sub = p.add_subparsers(dest="cmd", required=True)
    d = sub.add_parser("daemon", help="run the resolution daemon in "
                                      "the foreground")
    d.add_argument("--socket", default=None,
                   help="AF_UNIX path or host:port (default: the "
                        "store's canonical socket)")
    d.add_argument("--workers", type=int, default=None,
                   help="pool width (default: cores - 1, min 2)")
    d.add_argument("--store-dir", default=None,
                   help="rescache store directory to serve")
    d.add_argument("--max-queued-chunks", type=int, default=4096,
                   help="global admission cap on queued chunks")
    d.add_argument("--max-client-chunks", type=int, default=4096,
                   help="per-client outstanding-chunks budget")
    d.add_argument("--retry-budget", type=int, default=None,
                   help="chunk re-dispatches tolerated per job after "
                        "worker deaths")
    d.add_argument("--throttle", type=float, default=0.0,
                   help="seconds to sleep before each chunk dispatch "
                        "(test/debug knob)")
    d.add_argument("--no-journal", action="store_true",
                   help="disable the append-only journal (stats reset "
                        "on restart; in-flight jobs are not resumed)")
    d.add_argument("--speculate-after", type=float, default=None,
                   help="floor seconds before a straggling chunk earns "
                        "a speculative duplicate dispatch (0 disables; "
                        "default REPRO_SPECULATE_AFTER_S or 30)")
    d.add_argument("--speculate-factor", type=float, default=4.0,
                   help="chunk is a straggler past this multiple of "
                        "the observed median chunk wall")
    for name in ("stats", "shutdown"):
        sp = sub.add_parser(name)
        sp.add_argument("--socket", default=None)
    args = p.parse_args(argv)
    if args.cmd == "daemon":
        from ..core import rescache
        from ..serve import ResolutionDaemon
        if args.store_dir:
            rescache.configure(enabled=True, directory=args.store_dir)
        daemon = ResolutionDaemon(
            address=args.socket, workers=args.workers,
            max_queued_chunks=args.max_queued_chunks,
            max_client_chunks=args.max_client_chunks,
            retry_budget=args.retry_budget, throttle_s=args.throttle,
            journal=not args.no_journal,
            speculate_after_s=args.speculate_after,
            speculate_factor=args.speculate_factor)
        log.info("resolution daemon at %s (%d workers, store %s)",
                 daemon.address, daemon.workers, daemon.store_dir)
        daemon.serve_forever()
        return 0
    if args.cmd == "stats":
        from ..serve import ServeUnavailable, get_stats
        try:
            print(json.dumps(get_stats(args.socket), indent=2,
                             sort_keys=True))
        except ServeUnavailable as e:
            print(str(e), file=sys.stderr)
            return 1
        return 0
    from ..serve import shutdown
    ok = shutdown(args.socket)
    print("daemon stopped" if ok else "no daemon answered")
    return 0 if ok else 1


def _demo_main(argv: list[str]) -> None:
    import jax
    from ..compile_cache import enable_compile_cache
    from ..configs.base import load_config, reduced as reduce_config
    from ..models import init_params

    enable_compile_cache()

    p = argparse.ArgumentParser()
    p.add_argument("--arch", required=True)
    p.add_argument("--reduced", action="store_true")
    p.add_argument("--requests", type=int, default=4)
    p.add_argument("--prompt-len", type=int, default=16)
    p.add_argument("--gen", type=int, default=16)
    args = p.parse_args(argv)

    cfg = load_config(args.arch)
    if args.reduced:
        cfg = reduce_config(cfg)
    rng = np.random.default_rng(0)
    params = init_params(jax.random.PRNGKey(0), cfg)
    server = BatchedServer(cfg, params,
                           max_len=args.prompt_len + args.gen + 8)
    reqs = [Request(i, rng.integers(0, cfg.vocab_size,
                                    size=(args.prompt_len,)).astype(np.int32),
                    args.gen)
            for i in range(args.requests)]
    log.info("decode-step dataflow analysis:\n%s",
             server.dataflow_report(reqs))
    t0 = time.perf_counter()
    results = server.serve(reqs)
    dt = time.perf_counter() - t0
    tok_total = sum(len(r.tokens) for r in results)
    print(f"served {len(results)} requests, {tok_total} tokens "
          f"in {dt:.2f}s ({tok_total / dt:.1f} tok/s); "
          f"prefill {results[0].prefill_s:.3f}s, "
          f"decode {results[0].decode_s * 1e3:.1f} ms/tok")
    for r in results[:2]:
        print(f"  req {r.id}: {r.tokens[:8]}...")


def main(argv: list[str] | None = None) -> None:
    logging.basicConfig(level=logging.INFO)
    argv = sys.argv[1:] if argv is None else argv
    if argv and argv[0] in ("daemon", "stats", "shutdown"):
        raise SystemExit(_serve_cli(argv))
    _demo_main(argv)


if __name__ == "__main__":
    main()
