"""Driver for cells that serve a routed-expert (MoE) language model through
``ServeEngine``.

The window, the sample of finished requests and the comparison with the
plain reference are ``serve_lm``'s, imported. What differs:

* the configuration maps to a ``family="moe"`` ``ArchConfig`` with q/k
  norms and no biases (``arch_config``);
* the weights are made on the device from ``--seed`` a layer at a time:
  every leaf stacked over layers is allocated once in bfloat16 and one
  jitted call per layer writes that layer's slice of every such leaf in
  place, so no float32 copy of a whole stacked leaf (6.4 GB for the
  stacked ``we_gate``) is ever held (``make_weights``);
* operations and bytes come from ``bench/flops_moe.py`` and the routing's
  counters, which the engine puts on each ``serve.batch`` span: in a
  traced run they are read from the trace (``window_counters``), and the
  bytes of each call count only the experts that got a row;
* the readings a cell's limits are set from are taken here
  (``python3 -m bench.drivers.serve_moe --workload <cell> --seeds 1,2
  --seconds 3``, from the root of a checkout on the chip), since
  ``bench/calibrate.py`` drives ``serve_lm``. Besides the compared number
  and the control's, each seed's line gives how many of the sampled
  tokens' routings (token, layer) tip between the reference's float32
  router and a bfloat16 one (``route_tips`` of the reference).
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.drivers.serve_lm import (REF_TOKENS, Traffic, _instrument, _key,
                                    load_reference, prompt, sample_rows,
                                    serve_window, served_gaps)
from bench.trace import find_xplane, start_trace, stop_trace

__all__ = ["arch_config", "make_weights", "setup", "mean_gaps",
           "window_counters", "work", "run_cell", "readings"]

# names of the program's parameter leaves, by the law the benchmark fills
# them with: norm scales 1 + 0.1 N, and matrices N / sqrt(fan-in), with
# the fan-in axes named per leaf
_NORMS = {"ln1", "ln2", "final_norm", "q_norm", "k_norm"}
_FAN_IN = {"embed": (-1,), "head": (-2,), "wq": (-3,), "wk": (-3,),
           "wv": (-3,), "wo": (-3, -2), "router": (-2,), "we_gate": (-2,),
           "we_up": (-2,), "we_down": (-2,)}

# Hugging Face keys of the configuration -> the program's ArchConfig
_ARCH_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
              "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
              "moe_intermediate_size": "d_ff", "vocab_size": "vocab_size",
              "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
              "tie_word_embeddings": "tie_embeddings",
              "num_experts": "n_experts",
              "num_experts_per_tok": "experts_per_token"}

WINDOW = "bench.window"


def arch_config(name: str, cfg: dict):
    from repro.configs.base import ArchConfig

    if not cfg["norm_topk_prob"] or cfg.get("mlp_only_layers"):
        raise ValueError("the program renormalises the top-k gates and "
                         "routes every layer")
    return ArchConfig(name=name, family="moe", qkv_bias=False, qk_norm=True,
                      **{v: cfg[k] for k, v in _ARCH_KEYS.items()})


def _fill(name: str, key, shape, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if name in _NORMS:
        x = 1.0 + 0.1 * z
    else:
        x = z / np.sqrt(int(np.prod([shape[a] for a in _FAN_IN[name]])))
    return x.astype(dtype)


def _leaf_name(path) -> str:
    name = str(getattr(path[-1], "key", path[-1]))
    if name not in _NORMS | set(_FAN_IN):
        raise KeyError(f"no fill law for parameter {name!r}")
    return name


def make_weights(abstract, seed: int):
    """The program's parameter tree, filled from ``seed`` on the device in
    the type it is served in: the unstacked leaves in one jitted call,
    then each layer of the stacked ones (``segments``) in place."""
    key = _key(seed)
    top = {k: v for k, v in abstract.items() if k != "segments"}
    flat, treedef = jax.tree_util.tree_flatten_with_path(top)
    specs = [(_leaf_name(p), leaf.shape, leaf.dtype) for p, leaf in flat]

    @jax.jit
    def fill_top(key):
        return jax.tree_util.tree_unflatten(treedef, [
            _fill(n, jax.random.fold_in(key, i), s, dt)
            for i, (n, s, dt) in enumerate(specs)])

    params = dict(fill_top(key))
    segs = []
    for j, seg in enumerate(abstract["segments"]):
        sflat, sdef = jax.tree_util.tree_flatten_with_path(seg)
        names = [_leaf_name(p) for p, _ in sflat]
        n_layers = sflat[0][1].shape[0]
        skey = jax.random.fold_in(key, len(specs) + j)

        @functools.partial(jax.jit, donate_argnums=0)
        def fill_layer(bufs, skey, layer):
            out = []
            for i, (n, b) in enumerate(zip(names, bufs)):
                k = jax.random.fold_in(jax.random.fold_in(skey, i), layer)
                x = _fill(n, k, b.shape[1:], b.dtype)
                out.append(jax.lax.dynamic_update_index_in_dim(b, x, layer,
                                                               0))
            return out

        bufs = [jnp.zeros(leaf.shape, leaf.dtype) for _, leaf in sflat]
        for layer in range(n_layers):
            bufs = fill_layer(bufs, skey, layer)
        segs.append(jax.tree_util.tree_unflatten(sdef, bufs))
    params["segments"] = segs
    return jax.block_until_ready(params)


def setup(cell, seed: int, trace: bool, phases: Optional[dict] = None):
    """Model, weights and a warmed engine; returns (engine, decode_ctx).
    ``phases`` receives the seconds of each part of the set-up."""
    t0 = time.perf_counter()
    from repro.models import build_model
    from repro.serve.engine import ServeEngine

    cfg, tr = cell.config, Traffic.from_json(cell.traffic)
    model = build_model(arch_config(cell.config_name, cfg))
    t1 = time.perf_counter()
    params = make_weights(model.abstract(), seed)
    t2 = time.perf_counter()
    eng = ServeEngine(model, params, smax=tr.prompt_tokens + tr.new_tokens)
    decode_ctx: List[int] = []
    _instrument(eng, trace, decode_ctx, tr.prompt_tokens)
    # one batch of the window's shapes; its prompts are drawn apart from
    # the window's, and one decode step compiles the decode program
    for j in range(tr.batch):
        eng.submit(prompt(seed, 10 ** 12 + j, tr.prompt_tokens,
                          cfg["vocab_size"]),
                   max_new=min(tr.new_tokens, 2))
    eng.run(batch_size=tr.batch)
    decode_ctx.clear()
    if phases is not None:
        phases.update(import_program=t1 - t0, weights=t2 - t1,
                      warm_up=time.perf_counter() - t2)
    return eng, decode_ctx


@jax.jit
def _gap_sum(ref_logits, toks):
    """sum over positions of (best reference logit - logit of ``toks``)."""
    got = jnp.take_along_axis(ref_logits, toks[..., None], -1)[..., 0]
    return jnp.sum(jnp.max(ref_logits, -1) - got)


def mean_gaps(ref, cfg: dict, params, rows: List[tuple],
              control: bool = False):
    """Mean, over every served token of ``rows`` of (prompt, served
    tokens), of the gap by which its reference logit lies below the
    reference's best at its position; with ``control``, also the mean gap
    of the tokens that the float8 control puts first there.

    ``served_gaps`` gives the widest such gap. Where routing tips on a
    near tie (the eighth and ninth expert's router logits within bfloat16
    rounding of each other), a served token follows a slightly different
    layer than the float32 reference's and its gap is as wide as a
    pervasive error's; such tips are rare (a token's routing in a layer),
    so the mean tells them from an error that reaches every token."""
    if not rows:
        return (0.0, 0.0) if control else 0.0
    shapes = {(len(p), len(g)) for p, g in rows}
    if len(shapes) > 1:
        raise ValueError(f"rows of one shape only, not {sorted(shapes)}")
    (P, N), = shapes
    total, total_ctrl, n = 0.0, 0.0, 0
    per = max(1, REF_TOKENS // (P + N - 1))
    for i in range(0, len(rows), per):
        chunk = rows[i:i + per]
        seq = np.stack([np.concatenate([p, g[:-1]]) for p, g in chunk])
        served = jnp.asarray(np.stack([g for _, g in chunk]))
        ref_logits = ref.logits(cfg, params, seq, P - 1)
        total += float(_gap_sum(ref_logits, served))
        if control:
            ctrl = jnp.argmax(ref.logits(cfg, params, seq, P - 1,
                                         quant="fp8"), -1)
            total_ctrl += float(_gap_sum(ref_logits, ctrl))
        n += served.size
        del ref_logits
    return (total / n, total_ctrl / n) if control else total / n


def window_counters(path: str, window_span: str = WINDOW) -> Dict[str, int]:
    """The routing counters on the ``serve.batch`` spans of the traced
    window, summed over its batches (the largest group: the largest);
    with the count of such batches under ``batches``. Empty where the
    spans carry none."""
    from jax.profiler import ProfileData

    for plane in ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = list(line.events)
            win = [e for e in evs if e.name == window_span]
            if not win:
                continue
            lo, hi = win[0].start_ns, win[0].end_ns
            out: Dict[str, int] = {}
            n = 0
            for e in evs:
                if e.name != "serve.batch" or not (
                        lo <= 0.5 * (e.start_ns + e.end_ns) <= hi):
                    continue
                stats = {k: v for k, v in e.stats
                         if str(k).startswith("moe_")}
                if not stats:
                    return {}
                n += 1
                for k, v in stats.items():
                    out[k] = (max(out.get(k, 0), int(v))
                              if k.endswith("_largest")
                              else out.get(k, 0) + int(v))
            return dict(out, batches=n) if n else {}
    return {}


def work(cfg: dict, tr: Traffic, w, decode_ctx: List[int],
         counters: Dict[str, int]) -> dict:
    """Operations and bytes of what the window served: from shapes, and
    with the routing's counters the bytes of each step and the expert
    kernel's own operations and bytes."""
    from bench import flops_moe

    B, P = tr.batch, tr.prompt_tokens
    pre_f = w.batches * flops_moe.prefill_flops(cfg, B, P)
    dec_f = sum(flops_moe.decode_flops(cfg, B, c) for c in decode_ctx)
    out = {"flops": pre_f + dec_f}
    if counters.get("batches") != w.batches:
        return out
    experts = {ph: counters[f"moe_{ph}_experts"]
               for ph in ("prefill", "decode")}
    rows = {ph: counters[f"moe_{ph}_rows"] for ph in ("prefill", "decode")}
    ewb = flops_moe.expert_weight_bytes(cfg)
    out["prefill"] = {
        "calls": w.batches, "flops": pre_f,
        "bytes": (w.batches * flops_moe.prefill_bytes(cfg, B, P, 0)
                  + experts["prefill"] * ewb)}
    out["decode"] = {
        "calls": len(decode_ctx), "flops": dec_f,
        "bytes": (sum(flops_moe.decode_bytes(cfg, B, c, 0)
                      for c in decode_ctx) + experts["decode"] * ewb)}
    for ph, calls in (("prefill", w.batches), ("decode", len(decode_ctx))):
        out[f"experts_{ph}"] = {
            "calls": calls, "flops": flops_moe.expert_flops(cfg, rows[ph]),
            "bytes": flops_moe.expert_bytes(cfg, rows[ph], experts[ph]),
            "experts": experts[ph], "rows": rows[ph],
            "largest": counters[f"moe_{ph}_largest"]}
    return out


def run_cell(cell, seed: int, seconds: float, trace: bool, t_start: float,
             counter, trace_dir: Optional[Path] = None) -> dict:
    """Set up, measure the window, then compare with the reference."""
    cfg, tr = cell.config, Traffic.from_json(cell.traffic)
    phases = {"start_and_jax": time.perf_counter() - t_start}
    eng, decode_ctx = setup(cell, seed, trace, phases)
    c0 = counter.compiles
    setup_s = time.perf_counter() - t_start
    if trace:
        start_trace(trace_dir)
    w = serve_window(eng, tr, seed, seconds, cfg["vocab_size"])
    summary = stop_trace(trace_dir, WINDOW) if trace else None
    counters = (window_counters(find_xplane(str(trace_dir))) if trace
                else {})
    compiles = counter.compiles - c0
    devs = jax.devices()[:cell.chips]
    # the CPU backend of the tests reports no memory statistics
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs)
    params = eng.params
    del eng
    ref = load_reference(cell.config_file)
    rows = sample_rows(w.finished, tr.sample_requests, seed)
    gap = served_gaps(ref, cfg, params, rows)
    mean = mean_gaps(ref, cfg, params, rows)
    n_due = tr.batch + int(w.seconds * tr.rate_per_s)
    return {
        "metrics": {"tokens_per_s": w.tokens / w.seconds, "setup_s": setup_s},
        "attempted": w.attempted, "failed": w.failed,
        "memory_peak_bytes": peak, "compiles_in_window": compiles,
        "checks": {"max_logit_gap": gap, "mean_logit_gap": mean},
        "trace": summary, "window_s": w.seconds,
        "work": work(cfg, tr, w, decode_ctx, counters),
        "notes": [f"window {w.seconds:.3f}s: {w.batches} batches of "
                  f"{tr.batch}, {w.tokens} tokens, {w.attempted} requests "
                  f"({w.failed} failed); backlog at close "
                  f"{n_due - w.attempted} requests; seconds per batch "
                  f"min {min(w.batch_s):.4f} median "
                  f"{float(np.median(w.batch_s)):.4f} max "
                  f"{max(w.batch_s):.4f}; {len(w.gc_s)} collector pauses, "
                  f"longest {max(w.gc_s, default=0.0):.4f} s",
                  "set-up seconds: " + ", ".join(
                      f"{k} {v:.3f}" for k, v in phases.items())
                  + f"; {c0} compiles ({counter.compile_s:.2f} s), "
                  f"{counter.cache_hits} persistent-cache hits",
                  f"routing counters of the window: {counters or 'none'}",
                  f"reference: {len(rows)} requests, "
                  f"{sum(len(g) for _, g in rows)} served tokens compared"],
    }


def readings(cell, seeds: List[int], seconds: float, out=sys.stdout) -> dict:
    """For each seed, in one process through the cell's own engine and
    traffic: that seed's weights and prompts, a window of ``seconds``, the
    compared number of the sampled requests against the reference, the
    same number for the float8 control (the reference computed in float8
    in the program's place, at the same positions), and the routings of
    the sampled sequences that tip in bfloat16. One JSON line per seed;
    returns, for each compared number, the largest program reading and
    the smallest control's."""
    from bench.compiles import CompileCounter

    tr = Traffic.from_json(cell.traffic)
    cfg = cell.config
    ref = load_reference(cell.config_file)
    counter = CompileCounter()
    eng, _ = setup(cell, seeds[0], trace=False)
    prog, ctrl = [], []
    for s in seeds:
        if s != seeds[0]:
            eng.params = None
            eng.params = make_weights(eng.model.abstract(), s)
        c0 = counter.compiles
        w = serve_window(eng, tr, s, seconds, cfg["vocab_size"])
        compiles = counter.compiles - c0
        rows = sample_rows(w.finished, tr.sample_requests, s)
        t0 = time.perf_counter()
        g, c = served_gaps(ref, cfg, eng.params, rows, control=True)
        m, mc = mean_gaps(ref, cfg, eng.params, rows, control=True)
        seqs = np.stack([np.concatenate([p, t[:-1]]) for p, t in rows])
        tips, routings = ref.route_tips(cfg, eng.params, seqs)
        prog.append((g, m))
        ctrl.append((c, mc))
        print(json.dumps({
            "seed": s, "max_logit_gap": g, "control_max_logit_gap": c,
            "mean_logit_gap": m, "control_mean_logit_gap": mc,
            "route_tips": tips, "routings": routings,
            "tokens_per_s": w.tokens / w.seconds, "batches": w.batches,
            "batch_s_max": max(w.batch_s), "failed": w.failed,
            "compiles_in_window": compiles,
            "served_tokens_compared": sum(len(x[1]) for x in rows),
            "reference_s": time.perf_counter() - t0}), file=out, flush=True)
    summary = {"workload": cell.name, "seeds": len(seeds)}
    for j, name in enumerate(("max_logit_gap", "mean_logit_gap")):
        summary[name] = {"program_max": max(x[j] for x in prog),
                         "control_min": min(x[j] for x in ctrl)}
    print(json.dumps(summary), file=out, flush=True)
    return summary


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=readings.__doc__.split(".")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    root = Path(__file__).resolve().parents[2]
    sys.path.insert(1, str(root / "src"))
    cache = str(root / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache
    jax.config.update("jax_compilation_cache_dir", cache)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if jax.devices()[0].platform != "tpu":
        print("serve_moe: no TPU", file=sys.stderr)
        return 2
    from bench.run import BenchError, load_cell, make_cell

    try:
        cell, _ = load_cell(args.workload)
    except BenchError:
        config, traffic = args.workload.rsplit(".", 1)
        cell = make_cell(args.workload, config,
                         root / "bench" / "configs" / f"{config}.json",
                         traffic, 1)
    readings(cell, [int(s) for s in args.seeds.split(",")], args.seconds)
    return 0


if __name__ == "__main__":
    sys.exit(main())
