#!/usr/bin/env python3
"""On-chip smoke test: drive the system's main paths once on a TPU.

  python chip_smoke.py              # one chip: campaign, serving, kernels
  python chip_smoke.py --chips 4    # four chips: sharded qwen2-1.5b only

Phases on one chip, in order:

1. campaign — ``python -m repro.sweep run lm_full_pod --backend pool`` in
   this process, on a fresh result cache: qwen3-32b at published widths,
   13,824 pre-screen points and one refined point per cell. Every selected
   point must be refined with no failed record, every pre-screen output
   must live on the TPU, the pool must not have fallen back to inline
   refinement, and a re-run of the whole pre-screen on the host CPU must
   select exactly the same points.
2. serving — qwen2-1.5b at published widths with random bf16 weights from
   ``--seed``, through ``serve.ServeEngine``. The last decode step's logits
   are compared with a cache-free ``Model.prefill`` over prompt plus
   generated tokens.
3. kernels — the three Pallas kernels compiled (``interpret=False``) at
   real widths, each against its ``ref.py`` oracle.

With ``--chips 4`` the script runs only the sharded path: qwen2-1.5b
prefill plus a few decode steps through ``launch.programs.build_program``
on a 2x2 ("data", "model") mesh, compared with the same program on one
chip over the same weights and tokens.

One process holds the chip for the whole run. Each phase prints its wall
time and XLA compile count; these are single smoke readings, not
benchmark numbers. A phase that fails prints its traceback and the script
exits 1 after the remaining phases. Only when every phase passed is the
last line of stdout ``{"ok": true, "device": {...}}``. Without a TPU, or
run outside the repository, it exits non-zero before any phase.
"""
from __future__ import annotations

import argparse
import contextlib
import io
import json
import os
import shutil
import sys
import time
import traceback
from pathlib import Path

REPO = Path(__file__).resolve().parent
WORK_DIR = REPO / "benchmarks" / "artifacts" / "chip_smoke"
CAMPAIGN = "lm_full_pod"

# bf16 tolerances, fixed before the chip runs. Logit errors are taken
# relative to the largest reference logit of the same row.
LOGIT_TOL = 5e-2
SHARDED_LOGIT_TOL = 5e-2
FLASH_TOL = 2e-2          # of max |ref|
RMSNORM_TOL = 2e-2        # rtol = atol, as tests/test_kernels.py
SSM_TOL = 1e-4            # f32


class SmokeFailure(RuntimeError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class _Tee(io.TextIOBase):
    """Write to the real stdout and keep a copy."""

    def __init__(self, stream):
        self.stream = stream
        self.buf = io.StringIO()

    def write(self, s: str) -> int:
        self.stream.write(s)
        self.buf.write(s)
        return len(s)

    def flush(self) -> None:
        self.stream.flush()


class CompileCounter:
    """Counts XLA backend compiles and persistent-cache hits."""

    def __init__(self):
        import jax.monitoring as mon

        self.compiles = 0
        self.compile_s = 0.0
        self.cache_hits = 0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def snapshot(self):
        return self.compiles, self.compile_s, self.cache_hits


def _rel_err(out, ref):
    """max |out - ref| over max |ref|, per leading row, in f32."""
    import numpy as np

    out = np.asarray(out, np.float32).reshape(len(out), -1)
    ref = np.asarray(ref, np.float32).reshape(len(ref), -1)
    return np.abs(out - ref).max(1) / np.maximum(np.abs(ref).max(1), 1e-30)


# ---------------------------------------------------------------------------
# phase 1: the campaign (the main path)
# ---------------------------------------------------------------------------

@contextlib.contextmanager
def _record_prescreen_placement(platforms: list):
    """Record the platforms that hold each pre-screen output."""
    from repro.core import vectorized

    impl = vectorized._schedule_many_stats_impl

    def recording(*args, **kw):
        mk, busy = impl(*args, **kw)
        platforms.append({d.platform for d in mk.devices() | busy.devices()})
        return mk, busy

    vectorized._schedule_many_stats_impl = recording
    try:
        yield
    finally:
        vectorized._schedule_many_stats_impl = impl


def phase_campaign(work_dir: Path, platform: str) -> None:
    import jax
    import numpy as np

    from repro.sweep.__main__ import main as sweep_main
    from repro.sweep.pareto import select_points
    from repro.sweep.prescreen import prescreen_cell
    from repro.sweep.spec import load_spec

    if work_dir.exists():
        shutil.rmtree(work_dir)
    work_dir.mkdir(parents=True)
    out = work_dir / f"{CAMPAIGN}.json"

    dev_platforms: list = []
    tee = _Tee(sys.stdout)
    with _record_prescreen_placement(dev_platforms), \
            contextlib.redirect_stdout(tee):
        rc = sweep_main(["run", CAMPAIGN, "--backend", "pool",
                         "--cache-dir", str(work_dir / "sweep_cache"),
                         "--out", str(out)])
    check(rc == 0, f"sweep run exited {rc}")
    check("worker pool unavailable" not in tee.buf.getvalue(),
          "the process pool fell back to inline refinement")
    res = json.loads(out.read_text())
    recs, summary = res["records"], res["summary"]
    check(summary["backend"] == "pool", f"backend {summary['backend']}")
    failed = [r["point_id"] for r in recs if r.get("status") == "failed"]
    check(not failed, f"{len(failed)} failed records, e.g. {failed[:3]}")
    selected = [r for r in recs if r["selected"]]
    unrefined = [r["point_id"] for r in selected if not r["refined"]]
    check(selected and not unrefined,
          f"{len(unrefined)} of {len(selected)} selected points unrefined")
    check(summary["cache_hits"] == 0,
          f"{summary['cache_hits']} cache hits on a fresh cache")
    check(dev_platforms and all(p == {platform} for p in dev_platforms),
          f"pre-screen outputs on {dev_platforms}, not {platform}")
    print(f"campaign {CAMPAIGN}: {len(recs)} points, {summary['cells']} "
          f"cells, {len(selected)} selected, {summary['refined']} refined "
          f"({summary['simulated']} simulated, 0 failed), "
          f"{len(dev_platforms)} pre-screen XLA calls on {platform}, "
          f"prescreen_s={summary['prescreen_s']:.3f} "
          f"refine_s={summary['refine_s']:.3f}")

    # cross-check: the whole pre-screen again on the host CPU
    spec = load_spec(CAMPAIGN)
    cpu_platforms: list = []
    with _record_prescreen_placement(cpu_platforms), \
            jax.default_device(jax.devices("cpu")[0]):
        memo: dict = {}
        screens = [prescreen_cell(c, memo=memo) for c in spec.cells()]
    check(all(p == {"cpu"} for p in cpu_platforms),
          f"cpu re-run outputs on {cpu_platforms}")
    pos, rel, same_cells, flips = 0, [], 0, []
    for scr in screens:
        cell_recs = recs[pos:pos + len(scr.cell.points)]
        pos += len(cell_recs)
        t_dev = np.array([r["analytic_time_ns"] for r in cell_recs])
        e_dev = np.array([r["analytic_energy_j"] for r in cell_recs])
        rel.append(np.maximum(np.abs(t_dev - scr.time_ns) / scr.time_ns,
                              np.abs(e_dev - scr.energy_j) / scr.energy_j))
        sel_cpu = set(select_points(
            np.stack([scr.time_ns, scr.energy_j], axis=1),
            mode=spec.refine.mode, max_points=spec.refine.max_points))
        sel_dev = {i for i, r in enumerate(cell_recs) if r["selected"]}
        if sel_cpu == sel_dev:
            same_cells += 1
        else:
            flips.append((scr.cell.label, sorted(sel_dev), sorted(sel_cpu)))
    check(pos == len(recs), f"cross-check covered {pos} of {len(recs)}")
    rel = np.concatenate(rel)
    q = np.quantile(rel, [0.5, 0.9, 0.99, 1.0])
    print(f"prescreen {platform}-vs-cpu: largest relative difference per point "
          f"(time or energy) p50={q[0]:.3e} p90={q[1]:.3e} p99={q[2]:.3e} "
          f"max={q[3]:.3e}; {int((rel > 0).sum())}/{len(rel)} points differ; "
          f"{same_cells}/{len(screens)} cells select the same points")
    for label, dev, cpu in flips[:5]:
        print(f"  selection differs in {label}: {platform} {dev} cpu {cpu}")
    check(not flips, f"{len(flips)} cells select different points on cpu")


# ---------------------------------------------------------------------------
# phase 2: serving at full width
# ---------------------------------------------------------------------------

def phase_serving(cfg, *, seed: int, n_requests: int = 8,
                  prompt_len: int = 1024, max_new: int = 32,
                  n_ref: int = 2) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.models import build_model
    from repro.serve.engine import ServeEngine

    model = build_model(cfg)
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        jax.jit(model.init)(jax.random.PRNGKey(seed)))
    n_params = sum(x.size for x in jax.tree_util.tree_leaves(params))
    print(f"serving {cfg.name}: {n_params / 1e9:.3f}B bf16 params "
          f"initialised in {time.perf_counter() - t0:.2f}s")

    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab_size, (n_requests, prompt_len),
                           dtype=np.int32)
    smax = prompt_len + max_new
    eng = ServeEngine(model, params, smax=smax)
    served = {}
    decode = eng.decode_fn

    def recording_decode(p, cache, tok):
        logits, cache = decode(p, cache, tok)
        served["logits"] = logits
        return logits, cache

    eng.decode_fn = recording_decode
    rids = [eng.submit(p, max_new=max_new) for p in prompts]
    t0 = time.perf_counter()
    out = eng.run(batch_size=n_requests)
    wall = time.perf_counter() - t0
    check(sorted(out) == sorted(rids), "not every request was answered")
    gen = np.array([out[r] for r in rids], np.int32)
    check(gen.shape == (n_requests, max_new),
          f"generated shape {gen.shape}")
    check(((gen >= 0) & (gen < cfg.padded_vocab)).all(), "token out of range")
    last = np.asarray(served["logits"], np.float32)
    check(np.isfinite(last).all(), "non-finite decode logits")
    print(f"serving: {n_requests} requests x {prompt_len} prompt tokens, "
          f"{max_new} new tokens each, in {wall:.2f}s (compiles included)")

    # reference: a cache-free prefill over prompt + all but the last
    # generated token yields the logits the last decode step produced
    seq = np.concatenate([prompts[:n_ref], gen[:n_ref, :-1]], axis=1)
    ref_fn = jax.jit(model.prefill, static_argnums=2)
    ref_logits, _ = ref_fn(params, {"tokens": jnp.asarray(seq)}, smax)
    ref = np.asarray(ref_logits, np.float32)
    err = _rel_err(last[:n_ref], ref)
    agree = (ref.argmax(-1) == gen[:n_ref, -1]).mean()
    print(f"serving vs cache-free prefill ({n_ref} requests, "
          f"{seq.shape[1]} tokens): max|dlogit|/max|logit| per request "
          f"{np.array2string(err, precision=4)} (tol {LOGIT_TOL}); "
          f"last-token argmax agreement {agree:.2f}")
    check((err <= LOGIT_TOL).all(), "served logits off the reference")


# ---------------------------------------------------------------------------
# phase 3: kernels at real widths
# ---------------------------------------------------------------------------

def phase_kernels(*, seed: int, interpret: bool = False,
                  flash_shape=(2, 2048, 12, 2, 128), rms_shape=(8192, 1536),
                  ssm_shape=(4, 2048, 3200)) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.flash_attention.ops import flash_mha
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.rmsnorm.kernel import fused_rmsnorm
    from repro.kernels.rmsnorm.ref import rmsnorm_ref
    from repro.kernels.ssm_scan.ops import ssm_scan_batched
    from repro.kernels.ssm_scan.ref import ssm_scan_ref

    # inputs are made on the host and references run jitted, so the
    # phase compiles each kernel and each oracle once
    rng = np.random.default_rng(seed)

    def normal(shape, dtype):
        return jnp.asarray(rng.standard_normal(shape, np.float32)
                           .astype(dtype))

    # flash attention at qwen2-1.5b widths (GQA 12:2, hd 128, bf16)
    B, S, H, KV, hd = flash_shape
    q = normal((B, S, H, hd), jnp.bfloat16)
    k = normal((B, S, KV, hd), jnp.bfloat16)
    v = normal((B, S, KV, hd), jnp.bfloat16)
    t0 = time.perf_counter()
    out = jax.block_until_ready(
        flash_mha(q, k, v, causal=True, interpret=interpret))
    wall = time.perf_counter() - t0

    @jax.jit
    def mha_ref(q, k, v):
        def flat(x):
            return x.transpose(0, 2, 1, 3).reshape(-1, S, hd)

        o = attention_ref(flat(q), flat(k), flat(v),
                          n_q_heads_per_kv=H // KV, causal=True)
        return o.reshape(B, H, S, hd).transpose(0, 2, 1, 3)

    err = float(_rel_err(out[None], mha_ref(q, k, v)[None])[0])
    print(f"kernel flash_attention {flash_shape} bf16: "
          f"max|err|/max|ref|={err:.3e} (tol {FLASH_TOL}), "
          f"first call {wall:.3f}s")
    check(err <= FLASH_TOL, "flash attention off its oracle")

    # rmsnorm at qwen2-1.5b d_model
    x = normal(rms_shape, jnp.bfloat16)
    w = normal(rms_shape[-1:], jnp.bfloat16)
    t0 = time.perf_counter()
    out = jax.block_until_ready(fused_rmsnorm(x, w, interpret=interpret))
    wall = time.perf_counter() - t0
    out32 = np.asarray(out, np.float32)
    ref32 = np.asarray(jax.jit(rmsnorm_ref)(x, w), np.float32)
    bad = ~np.isclose(out32, ref32, rtol=RMSNORM_TOL, atol=RMSNORM_TOL)
    print(f"kernel rmsnorm {rms_shape} bf16: max|err|="
          f"{np.abs(out32 - ref32).max():.3e}, {int(bad.sum())} elements "
          f"outside rtol=atol={RMSNORM_TOL}, first call {wall:.3f}s")
    check(not bad.any(), "rmsnorm off its oracle")

    # ssm_scan at hymba-1.5b's inner width (ssm_expand x d_model)
    a = jnp.asarray(1.0 / (1.0 + np.exp(
        -rng.standard_normal(ssm_shape, np.float32))))
    b = normal(ssm_shape, jnp.float32)
    t0 = time.perf_counter()
    out = jax.block_until_ready(ssm_scan_batched(a, b, interpret=interpret))
    wall = time.perf_counter() - t0
    out32 = np.asarray(out)
    ref32 = np.asarray(jax.jit(jax.vmap(ssm_scan_ref))(a, b))
    bad = ~np.isclose(out32, ref32, rtol=SSM_TOL, atol=SSM_TOL)
    print(f"kernel ssm_scan {ssm_shape} f32: max|err|="
          f"{np.abs(out32 - ref32).max():.3e}, {int(bad.sum())} elements "
          f"outside rtol=atol={SSM_TOL}, first call {wall:.3f}s")
    check(not bad.any(), "ssm_scan off its oracle")


# ---------------------------------------------------------------------------
# --chips 4: the sharded serving programs
# ---------------------------------------------------------------------------

def _serve_programs(cfg, mesh, params, tokens, smax: int, feed):
    """Prefill, then one decode step per token row in ``feed``, through
    build_program on ``mesh``; returns the logits of every step
    [len(feed) + 1, B, V]."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.base import ShapeSpec
    from repro.launch.programs import build_program

    B = tokens.shape[0]
    pre = build_program(cfg, ShapeSpec("smoke_prefill", smax, B, "prefill"),
                        mesh)
    dec = build_program(cfg, ShapeSpec("smoke_decode", smax, B, "decode"),
                        mesh)
    p = jax.device_put(params, pre.in_shardings[0])
    batch = jax.device_put({"tokens": jnp.asarray(tokens)},
                           pre.in_shardings[1])
    logits, cache = pre.jitted()(p, batch)
    steps = [np.asarray(logits, np.float32)]
    step = dec.jitted()
    for tok in feed:
        logits, cache = step(p, cache,
                             jax.device_put(tok, dec.in_shardings[2]))
        steps.append(np.asarray(logits, np.float32))
    return np.stack(steps)


def phase_sharded(cfg, *, seed: int, batch: int = 4, prompt_len: int = 1024,
                  n_decode: int = 4) -> None:
    import jax
    import numpy as np

    from repro.launch.mesh import make_mesh
    from repro.models import build_model

    check(len(jax.devices()) >= 4, f"{len(jax.devices())} devices, need 4")
    params = jax.jit(build_model(cfg).init)(jax.random.PRNGKey(seed))
    rng = np.random.default_rng(seed)
    tokens = rng.integers(0, cfg.vocab_size, (batch, prompt_len),
                          dtype=np.int32)
    # both meshes decode the same token rows, so their logits stay
    # comparable step by step
    feed = rng.integers(0, cfg.vocab_size, (n_decode, batch, 1),
                        dtype=np.int32)
    smax = prompt_len + n_decode
    t0 = time.perf_counter()
    one = _serve_programs(cfg, make_mesh((1, 1), ("data", "model")),
                          params, tokens, smax, feed)
    t1 = time.perf_counter()
    four = _serve_programs(cfg, make_mesh((2, 2), ("data", "model")),
                           params, tokens, smax, feed)
    t2 = time.perf_counter()
    check(np.isfinite(one).all() and np.isfinite(four).all(),
          "non-finite logits")
    err = np.stack([_rel_err(a, b) for a, b in zip(four, one)])  # [steps, B]
    agree = (four.argmax(-1) == one.argmax(-1)).mean()
    print(f"sharded {cfg.name}: batch {batch}, prompt {prompt_len}, "
          f"{n_decode} decode steps; 1x1 mesh {t1 - t0:.2f}s, 2x2 "
          f"(data, model) mesh {t2 - t1:.2f}s (compiles included)")
    print(f"sharded vs one chip: max|dlogit|/max|logit| per step "
          f"{np.array2string(err.max(1), precision=4)} (tol "
          f"{SHARDED_LOGIT_TOL}); argmax agreement {agree:.2f}")
    check((err <= SHARDED_LOGIT_TOL).all(), "sharded logits off one chip")


# ---------------------------------------------------------------------------

def _run_phases(phases, counter) -> list:
    failed = []
    for name, fn in phases:
        c0, s0, h0 = counter.snapshot()
        t0 = time.perf_counter()
        try:
            fn()
            status = "ok"
        except Exception:
            traceback.print_exc()
            failed.append(name)
            status = "FAILED"
        c1, s1, h1 = counter.snapshot()
        print(f"phase {name}: {status} wall_s={time.perf_counter() - t0:.3f} "
              f"xla_compiles={c1 - c0} compile_s={s1 - s0:.3f} "
              f"persistent_cache_hits={h1 - h0}", flush=True)
    return failed


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the sharded 2x2-mesh serving phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (REPO / "src" / "repro").is_dir():
        print(f"chip_smoke: {REPO / 'src' / 'repro'} not found; run this "
              f"script from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(REPO / "src"))
    # the campaign cross-check needs the host CPU backend beside the TPU
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"

    import jax

    devs = jax.devices()
    d0 = devs[0]
    print(f"device platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    if d0.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{d0.platform}); this smoke runs on the chip only",
              file=sys.stderr)
        return 1
    if len(devs) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devs)}", file=sys.stderr)
        return 1

    from repro.configs import REGISTRY
    from repro.launch.compile_cache import enable_compile_cache

    print(f"compile cache: {enable_compile_cache()}")
    counter = CompileCounter()
    qwen2 = REGISTRY["qwen2-1.5b"]
    if args.chips == 4:
        phases = [("sharded", lambda: phase_sharded(qwen2, seed=args.seed))]
    else:
        phases = [
            ("campaign", lambda: phase_campaign(WORK_DIR, d0.platform)),
            ("serving", lambda: phase_serving(qwen2, seed=args.seed)),
            ("kernels", lambda: phase_kernels(seed=args.seed)),
        ]
    failed = _run_phases(phases, counter)
    if failed:
        print(f"chip_smoke: failed phases: {', '.join(failed)}",
              file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
