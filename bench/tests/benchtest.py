"""Helpers for the benchmark's own tests: a tiny copy of ``bench/`` with
cells small enough for the CPU, and a helper that drives one run of it
with the look for a chip skipped."""

from __future__ import annotations

import contextlib
import io
import json
import os
import shutil
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

#: tiny stand-ins for the configurations and traffic (same code paths)
TINY_SPMV = {"name": "spmv-tiny", "dim": 64, "iterations": 20000}
#: a cache smaller than the tiny vector, so that lines are evicted and
#: the FIFOs fill
TINY_CACHE_BYTES = 128
TINY_OLMO = {"name": "olmo-tiny", "d_model": 64, "n_heads": 4,
             "n_layers": 2, "mlp_hidden_size": 256, "vocab_size": 250,
             "embedding_size": 256, "init_std": 0.1}
#: the program's configuration cut to the tiny sizes; its layer segments
#: are rebuilt from ``num_layers``
TINY_OLMO_PROGRAM = {"d_model": 64, "num_heads": 4, "num_kv_heads": 4,
                     "head_dim": 16, "num_layers": 2, "d_ff": 128,
                     "vocab_size": 256, "segments": []}
#: the tiny model's limit: sound runs read 0.0, the fp8 control about 0.19
#: and each fault of test_faults.py 1.9 or more
TINY_DECODE = {"batch": 4, "prompt_lengths": [8, 16], "new_tokens": [4, 12],
               "max_len": 32, "token_ids": 250, "sample_tokens": 20,
               "reference_rows": 2, "logit_gap_limit": 0.1}


#: made-up peaks for the CPU, in the tiny copy only, so that the readers
#: of shares of a peak run in tests (no CPU number is ever reported)
TEST_CPU_PEAKS = {"bf16_flops_per_s": 1e12}


def _load(path):
    with open(path) as f:
        return json.load(f)


def _dump(obj, path):
    with open(path, "w") as f:
        json.dump(obj, f, indent=1)


def make_tiny_bench(root) -> str:
    """A copy of the benchmark under ``root`` whose cells keep their
    names but run the tiny configurations; returns its ``bench`` dir."""
    bench = os.path.join(root, "bench")
    shutil.copytree(BENCH, bench,
                    ignore=shutil.ignore_patterns("tests", "__pycache__"))
    cdir, tdir = os.path.join(bench, "configs"), os.path.join(bench,
                                                              "traffic")
    spmv = _load(os.path.join(cdir, "spmv-table1.json"))
    spmv.update(TINY_SPMV)
    for mem in spmv["memory_models"].values():
        mem["cache"]["size_bytes"] = TINY_CACHE_BYTES
    _dump(spmv, os.path.join(cdir, "spmv-tiny.json"))
    shutil.copy(os.path.join(cdir, "spmv-table1.py"),
                os.path.join(cdir, "spmv-tiny.py"))
    olmo = _load(os.path.join(cdir, "olmo-1b.json"))
    olmo.update(TINY_OLMO)
    olmo["program"]["overrides"].update(TINY_OLMO_PROGRAM)
    _dump(olmo, os.path.join(cdir, "olmo-tiny.json"))
    shutil.copy(os.path.join(cdir, "olmo-1b.py"),
                os.path.join(cdir, "olmo-tiny.py"))
    decode = _load(os.path.join(tdir, "decode-heavy.json"))
    decode.update(TINY_DECODE)
    _dump(decode, os.path.join(tdir, "decode-tiny.json"))
    peaks = _load(os.path.join(bench, "peaks.json"))
    peaks["devices"]["cpu"] = TEST_CPU_PEAKS
    _dump(peaks, os.path.join(bench, "peaks.json"))
    spec = _load(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json"))
    tiny = {"spmv-table1": "spmv-tiny", "olmo-1b": "olmo-tiny",
            "decode-heavy": "decode-tiny"}
    for w in spec["workloads"]:
        w["config"] = tiny.get(w["config"], w["config"])
        w["traffic"] = tiny.get(w["traffic"], w["traffic"])
    _dump(spec, os.path.join(root, "BENCHMARK.json"))
    return bench


def load_driver(name: str):
    """The driver module ``drivers/<name>.py`` of the benchmark."""
    import harness
    return harness.load_module(os.path.join(BENCH, "drivers", name + ".py"))


def drive(monkeypatch, bench_dir: str, workload: str, *,
          seed: int = 3_000_000_019, seconds: float = 1.0,
          trace: int = 0) -> dict:
    """One run of ``workload`` in the copy at ``bench_dir`` on this
    process's CPU, the look for a chip skipped; returns the result line
    with ``rc``."""
    import jax

    import flops
    import harness
    import run

    out = io.StringIO()
    with contextlib.ExitStack() as stack:
        stack.enter_context(contextlib.redirect_stdout(out))
        monkeypatch.setattr(harness, "enable_compile_cache", lambda: None)
        monkeypatch.setattr(flops, "PEAKS",
                            os.path.join(bench_dir, "peaks.json"))
        rc = run.main(["--workload", workload, "--seed", str(seed),
                       "--seconds", str(seconds), "--trace", str(trace)],
                      require_chips=lambda n: jax.devices()[:n],
                      bench_dir=bench_dir)
    lines = out.getvalue().strip().splitlines()
    result = json.loads(lines[-1]) if lines else {}
    result["rc"] = rc
    return result
