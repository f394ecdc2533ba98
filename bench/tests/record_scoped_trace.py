#!/usr/bin/env python3
"""Record ``data/scoped_tpu.xplane.pb``, the trace that
``test_scopes.py`` reads, on one TPU:

    python3 bench/tests/record_scoped_trace.py --out <path>

A 2-layer model at qwen2-1.5b widths (``bench/configs/qwen2-1.5b.json``
with ``num_hidden_layers`` 2, weights from seed 0 as the benchmark makes
them) serves two batches of 4 requests, 128-token prompts and 8 new
tokens each, through ``ServeEngine`` under the host span
``bench.window``, after one warm-up batch outside the trace. The program
sets its scopes and the engine its ``serve.*`` spans. Prints the scope
summary of the window.
"""
from __future__ import annotations

import argparse
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    import jax

    from bench.drivers.serve_lm import arch_config, make_weights, prompt
    from bench.metrics._scopes import summarize
    from bench.trace import find_xplane, start_trace
    from repro.models import build_model
    from repro.serve.engine import ServeEngine

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    cfg = json.loads((ROOT / "bench/configs/qwen2-1.5b.json").read_text())
    cfg["num_hidden_layers"] = 2
    model = build_model(arch_config("qwen2-1.5b", cfg))
    params = make_weights(model.abstract(), 0)
    eng = ServeEngine(model, params, smax=136)

    def serve(first: int, n: int) -> None:
        for i in range(first, first + n):
            eng.submit(prompt(0, i, 128, cfg["vocab_size"]), max_new=8)
        eng.run(batch_size=4)

    serve(100, 4)                       # warm-up: compiles every program
    log_dir = ROOT / ".bench_out" / "record"
    start_trace(log_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        serve(0, 8)
    jax.profiler.stop_trace()
    path = find_xplane(str(log_dir))
    Path(args.out).parent.mkdir(parents=True, exist_ok=True)
    shutil.copyfile(path, args.out)
    s = summarize(args.out)
    print(json.dumps({
        "window_s": s.window_s, "busy_s": s.busy_s,
        "executions": s.executions, "offset_ns": s.offset_ns,
        "op_s": {f"{p} {sc}": v for (p, sc), v in sorted(s.op_s.items())},
        "gap_s": s.gap_s}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
