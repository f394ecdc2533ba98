"""Driver for cells that serve a language model through ``ServeEngine``.

Set-up (counted in ``setup_s``): build the program's model from the
configuration, make the weights on the device in one jitted call from
``--seed``, build one ``ServeEngine`` and run one batch of the cell's
shapes through it, so that every program the window uses is compiled
(or loaded from the persistent cache) before the window opens.

The window: requests arrive at the traffic's fixed rate (the first
batch at once), each with a prompt drawn from the seed and its index. The rate lies above what the engine sustains, so a full batch
is always waiting. Whenever ``batch`` requests have arrived, they are
submitted and ``ServeEngine.run`` serves them; after the window's last
second the batch in flight is finished and its time counted.
``tokens_per_s`` is prompt plus generated tokens of every batch served,
over the time from the window's start to the last batch's end.

Correctness, after the window: a sample of finished requests drawn from
the seed (the longest among them) is run through the configuration's
plain reference (``configs/<config>.ref.py``) over prompt plus served
tokens. The compared number is the widest gap by which a served token's
reference logit lies below the reference's best logit at its position.
"""
from __future__ import annotations

import gc
import importlib.util
import time
from collections import deque
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from bench.trace import start_trace, stop_trace

__all__ = ["Traffic", "arch_config", "make_weights", "prompt", "run_cell",
           "served_gaps", "load_reference"]

# names of the program's parameter leaves, by the law the benchmark fills
# them with: norm scales 1 + 0.1 N, biases 0.2 N, and matrices
# N / sqrt(fan-in), with the fan-in axes named per leaf
_NORMS = {"ln1", "ln2", "final_norm"}
_BIASES = {"bq", "bk", "bv"}
_FAN_IN = {"embed": (-1,), "wq": (-3,), "wk": (-3,), "wv": (-3,),
           "wo": (-3, -2), "wg": (-2,), "wi": (-2,), "wo2": (-2,)}

# rows x positions of one reference call: the reference's attention
# scores of one call then take at most 12 heads x 4096^2 x 4 B = 0.8 GB
REF_TOKENS = 4096

# Hugging Face keys of the configuration -> the program's ArchConfig
_ARCH_KEYS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
              "num_attention_heads": "n_heads",
              "num_key_value_heads": "n_kv_heads",
              "intermediate_size": "d_ff", "vocab_size": "vocab_size",
              "rope_theta": "rope_theta", "rms_norm_eps": "norm_eps",
              "tie_word_embeddings": "tie_embeddings"}


@dataclass(frozen=True)
class Traffic:
    prompt_tokens: int
    new_tokens: int
    batch: int
    rate_per_s: float
    sample_requests: int

    @classmethod
    def from_json(cls, d: dict) -> "Traffic":
        return cls(**{k: d[k] for k in cls.__dataclass_fields__ if k in d})

    def due(self, i: int) -> float:
        """Seconds after the window opens at which request ``i`` arrives."""
        return max(0, i - self.batch + 1) / self.rate_per_s


def arch_config(name: str, cfg: dict):
    from repro.configs.base import ArchConfig

    return ArchConfig(name=name, family="dense", qkv_bias=True,
                      **{v: cfg[k] for k, v in _ARCH_KEYS.items()})


def _key(seed: int):
    seed %= 2 ** 64
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def make_weights(abstract, seed: int):
    """The program's parameter tree, filled from ``seed`` in one jitted
    call on the device, in the type it is served in."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(abstract)
    specs = []
    for path, leaf in flat:
        name = str(getattr(path[-1], "key", path[-1]))
        if name not in _NORMS | _BIASES | set(_FAN_IN):
            raise KeyError(f"no fill law for parameter {name!r}")
        specs.append((name, leaf.shape, leaf.dtype))

    def fill(key):
        out = []
        for i, (name, shape, dtype) in enumerate(specs):
            z = jax.random.normal(jax.random.fold_in(key, i), shape,
                                  jnp.float32)
            if name in _NORMS:
                x = 1.0 + 0.1 * z
            elif name in _BIASES:
                x = 0.2 * z
            else:
                fan = int(np.prod([shape[a] for a in _FAN_IN[name]]))
                x = z / np.sqrt(fan)
            out.append(x.astype(dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    return jax.block_until_ready(jax.jit(fill)(_key(seed)))


def prompt(seed: int, i: int, length: int, vocab: int) -> np.ndarray:
    """Prompt ``i`` of the run with ``seed``: the same on every run."""
    rng = np.random.default_rng([seed % 2 ** 64, i])
    return rng.integers(0, vocab, length, dtype=np.int32)


def load_reference(config_file: Path):
    path = config_file.with_suffix(".ref.py")
    spec = importlib.util.spec_from_file_location(
        "bench_ref_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@jax.jit
def _widest_gap(ref_logits, toks):
    """max over positions of (best reference logit - logit of ``toks``)."""
    got = jnp.take_along_axis(ref_logits, toks[..., None], -1)[..., 0]
    return jnp.max(jnp.max(ref_logits, -1) - got)


def served_gaps(ref, cfg: dict, params, rows: List[tuple],
                control: bool = False):
    """Widest gap by which a served token's reference logit lies below the
    reference's best, over ``rows`` of (prompt, served tokens). With
    ``control``, also the widest gap of the token that the float8 control
    puts first at the same positions."""
    widest, widest_ctrl = 0.0, 0.0
    by_shape: Dict[tuple, list] = {}
    for p, g in rows:
        by_shape.setdefault((len(p), len(g)), []).append((p, g))
    for (P, N), group in sorted(by_shape.items()):
        per = max(1, REF_TOKENS // (P + N - 1))
        for i in range(0, len(group), per):
            chunk = group[i:i + per]
            seq = np.stack([np.concatenate([p, g[:-1]]) for p, g in chunk])
            served = jnp.asarray(np.stack([g for _, g in chunk]))
            ref_logits = ref.logits(cfg, params, seq, P - 1)
            widest = max(widest, float(_widest_gap(ref_logits, served)))
            if control:
                ctrl = jnp.argmax(ref.logits(cfg, params, seq, P - 1,
                                             quant="fp8"), -1)
                widest_ctrl = max(widest_ctrl,
                                  float(_widest_gap(ref_logits, ctrl)))
            del ref_logits
    return (widest, widest_ctrl) if control else widest


@dataclass
class Window:
    """What the measured window served."""
    t0: float = 0.0
    t_end: float = 0.0
    batches: int = 0
    tokens: int = 0
    attempted: int = 0
    failed: int = 0
    batch_s: List[float] = field(default_factory=list)
    gc_s: List[float] = field(default_factory=list)    # collector pauses
    finished: List[tuple] = field(default_factory=list)  # (prompt, tokens)

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0


def _instrument(eng, spans: bool, decode_ctx: List[int], prompt_tokens: int):
    """Wrap the engine's step functions from outside: a host span around
    each call in traced runs, and the context length of every decode
    step (the positions it attends, its own included)."""
    prefill, decode = eng.prefill_fn, eng.decode_fn
    ctx = [prompt_tokens]

    def prefill_fn(*a):
        ctx[0] = prompt_tokens
        if not spans:
            return prefill(*a)
        with jax.profiler.TraceAnnotation("bench.prefill"):
            return prefill(*a)

    def decode_fn(*a):
        ctx[0] += 1
        decode_ctx.append(ctx[0])
        if not spans:
            return decode(*a)
        with jax.profiler.TraceAnnotation("bench.decode"):
            return decode(*a)

    eng.prefill_fn, eng.decode_fn = prefill_fn, decode_fn


def serve_window(eng, tr: Traffic, seed: int, seconds: float,
                 vocab: int) -> Window:
    """Offer the traffic for ``seconds``, then finish the batch in flight."""
    w = Window()
    queue: deque = deque()
    nxt = 0
    gc_start = [0.0]

    def on_gc(phase, info):
        if phase == "start":
            gc_start[0] = time.perf_counter()
        else:
            w.gc_s.append(time.perf_counter() - gc_start[0])

    gc.callbacks.append(on_gc)
    with jax.profiler.TraceAnnotation("bench.window"):
        w.t0 = time.perf_counter()
        deadline = w.t0 + seconds
        while True:
            now = time.perf_counter()
            if w.batches and now >= deadline:
                break
            while w.t0 + tr.due(nxt) <= now:
                queue.append(nxt)
                nxt += 1
            if len(queue) < tr.batch:
                wake = w.t0 + tr.due(nxt + tr.batch - len(queue) - 1)
                with jax.profiler.TraceAnnotation("bench.wait"):
                    time.sleep(max(0.0, wake - now))
                continue
            ids = [queue.popleft() for _ in range(tr.batch)]
            tb = time.perf_counter()
            with jax.profiler.TraceAnnotation("bench.batch"):
                prompts = [prompt(seed, i, tr.prompt_tokens, vocab)
                           for i in ids]
                rids = [eng.submit(p, max_new=tr.new_tokens)
                        for p in prompts]
                out = eng.run(batch_size=tr.batch)
            w.batch_s.append(time.perf_counter() - tb)
            w.batches += 1
            w.attempted += len(rids)
            for p, rid in zip(prompts, rids):
                toks = out.get(rid)
                if toks is None or len(toks) != tr.new_tokens:
                    w.failed += 1
                    continue
                w.tokens += len(p) + len(toks)
                w.finished.append((p, np.asarray(toks, np.int32)))
        w.t_end = time.perf_counter()
    gc.callbacks.remove(on_gc)
    return w


def sample_rows(finished: List[tuple], n: int, seed: int) -> List[tuple]:
    """``n`` finished requests drawn from the seed, the longest among them."""
    if not finished:
        return []
    rng = np.random.default_rng([seed % 2 ** 64, 1])
    n = min(n, len(finished))
    idx = [int(i) for i in rng.choice(len(finished), n, replace=False)]
    longest = max(range(len(finished)),
                  key=lambda i: len(finished[i][0]) + len(finished[i][1]))
    if longest not in idx:
        idx[0] = longest
    return [finished[i] for i in sorted(idx)]


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


def work(cfg: dict, tr: Traffic, w: Window, decode_ctx: List[int]) -> dict:
    """Operations and bytes of what the window served, from shapes."""
    from bench import flops

    B, P = tr.batch, tr.prompt_tokens
    pre_f = w.batches * flops.prefill_flops(cfg, B, P)
    dec_f = sum(flops.decode_flops(cfg, B, c) for c in decode_ctx)
    return {
        "flops": pre_f + dec_f,
        "prefill": {"calls": w.batches, "flops": pre_f,
                    "bytes": w.batches * flops.prefill_bytes(cfg, B, P)},
        "decode": {"calls": len(decode_ctx), "flops": dec_f,
                   "bytes": sum(flops.decode_bytes(cfg, B, c)
                                for c in decode_ctx)},
    }


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
    summary = stop_trace(trace_dir, "bench.window") if trace else None
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
    n_due = tr.batch + int(w.seconds * tr.rate_per_s)
    return {
        "metrics": {"tokens_per_s": w.tokens / w.seconds, "setup_s": setup_s},
        "attempted": w.attempted, "failed": w.failed,
        "memory_peak_bytes": peak, "compiles_in_window": compiles,
        "checks": {"max_logit_gap": gap},
        "trace": summary, "window_s": w.seconds,
        "work": work(cfg, tr, w, decode_ctx),
        "notes": [f"window {w.seconds:.3f}s: {w.batches} batches of "
                  f"{tr.batch}, {w.tokens} tokens, {w.attempted} requests "
                  f"({w.failed} failed); backlog at close "
                  f"{n_due - w.attempted} requests; seconds per batch "
                  f"min {min(w.batch_s):.4f} median "
                  f"{float(np.median(w.batch_s)):.4f} max "
                  f"{max(w.batch_s):.4f}; {len(w.gc_s)} collector pauses, "
                  f"longest {max(w.gc_s, default=0.0):.4f} s, "
                  f"{sum(w.gc_s):.4f} s in all",
                  "set-up seconds: " + ", ".join(
                      f"{k} {v:.3f}" for k, v in phases.items())
                  + f"; {c0} compiles ({counter.compile_s:.2f} s), "
                  f"{counter.cache_hits} persistent-cache hits",
                  f"reference: {len(rows)} requests, "
                  f"{sum(len(g) for _, g in rows)} served tokens compared"],
    }
