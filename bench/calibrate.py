#!/usr/bin/env python3
"""Readings that a cell's limits are set from, on the chip.

    python3 bench/calibrate.py --workload <name> --seeds 1,2,3 --seconds 3

``<name>`` is a cell of ``BENCHMARK.json``, or ``<config>.<traffic>`` for
one that has no entry or limits yet (one chip).

For each seed, in one process and through the cell's own engine and
traffic: the weights and prompts of that seed, a measured window of
``--seconds`` at the cell's load, and the compared number of the sampled
requests against the plain reference, and the same number for the
control: the reference computed in float8 put in the
program's place, read at the same prompts and served tokens. One JSON
line per seed, then the largest program reading and the smallest control
reading. The benchmark's own runs never run the control.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:] = [str(ROOT), str(ROOT / "src")] + [
        p for p in sys.path if Path(p or ".").resolve() != BENCH]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(ROOT / ".jax_cache")

    import jax

    from bench.compiles import CompileCounter
    from bench.drivers import serve_lm
    from bench.run import BenchError, load_cell, make_cell

    jax.config.update("jax_compilation_cache_dir", str(ROOT / ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("calibrate: no TPU", file=sys.stderr)
        return 2
    try:
        cell, _ = load_cell(args.workload)
    except BenchError:
        config, traffic = args.workload.rsplit(".", 1)
        cell = make_cell(args.workload, config,
                         BENCH / "configs" / f"{config}.json", traffic, 1)
    tr = serve_lm.Traffic.from_json(cell.traffic)
    cfg = cell.config
    ref = serve_lm.load_reference(cell.config_file)
    seeds = [int(s) for s in args.seeds.split(",")]
    counter = CompileCounter()
    eng, decode_ctx = serve_lm.setup(cell, seeds[0], trace=False)
    print(f"setup {time.perf_counter() - T_START:.2f}s", file=sys.stderr)
    prog, ctrl = [], []
    for s in seeds:
        if s != seeds[0]:
            eng.params = None
            eng.params = serve_lm.make_weights(eng.model.abstract(), s)
        c0 = counter.compiles
        w = serve_lm.serve_window(eng, tr, s, args.seconds, cfg["vocab_size"])
        compiles = counter.compiles - c0
        rows = serve_lm.sample_rows(w.finished, tr.sample_requests, s)
        t0 = time.perf_counter()
        g, c = serve_lm.served_gaps(ref, cfg, eng.params, rows, control=True)
        prog.append(g)
        ctrl.append(c)
        print(json.dumps({
            "seed": s, "max_logit_gap": g, "control_max_logit_gap": c,
            "tokens_per_s": w.tokens / w.seconds, "batches": w.batches,
            "batch_s_max": max(w.batch_s), "gc_s_max": max(w.gc_s, default=0.0),
            "failed": w.failed, "compiles_in_window": compiles,
            "served_tokens_compared": sum(len(x[1]) for x in rows),
            "reference_s": time.perf_counter() - t0}), flush=True)
    print(json.dumps({"workload": cell.name, "seeds": len(seeds),
                      "program_max": max(prog),
                      "control_min": min(ctrl)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
