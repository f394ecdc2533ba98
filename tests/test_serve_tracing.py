"""Tracing of the serving path: the model's program scopes in the compiled
HLO, and the engine's ``serve.*`` spans in a profiler trace on the CPU."""
import dataclasses
import glob
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import REGISTRY
from repro.models import build_model
from repro.models.scopes import SCOPES
from repro.serve.engine import ServeEngine, make_decode_fn, make_prefill_fn

B, S, SMAX, NEW = 2, 8, 16, 4
PHASES = ("serve.prefill", "serve.sample", "serve.schedule", "serve.decode")


def _dense2():
    return dataclasses.replace(REGISTRY["qwen2-1.5b"].reduced(), n_layers=2)


def _op_names(cfg, program):
    model = build_model(cfg, remat=False)
    params = model.abstract(jnp.float32)
    if program == "prefill":
        batch = {"tokens": jax.ShapeDtypeStruct((B, S), jnp.int32)}
        if cfg.family == "vlm":
            batch["images"] = jax.ShapeDtypeStruct(
                (B, cfg.n_image_tokens, cfg.d_model), jnp.float32)
        fn, args = make_prefill_fn(model, None, SMAX), (params, batch)
    else:
        fn = make_decode_fn(model, None)
        args = (params, model.abstract_cache(B, SMAX, jnp.float32),
                jax.ShapeDtypeStruct((B, 1), jnp.int32))
    text = jax.jit(fn).lower(*args).compile().as_text()
    return re.findall(r'op_name="([^"]*)"', text)


def _innermost(path):
    return next((p for p in reversed(path.split("/")) if p in SCOPES), None)


@pytest.mark.parametrize("program", ["prefill", "decode"])
def test_dense_program_carries_every_model_scope(program):
    names = _op_names(_dense2(), program)
    found = {_innermost(n) for n in names}
    for scope in ("embed", "layers", "attn", "kv_write", "mlp",
                  "final_norm", "lm_head"):
        assert scope in found, scope
    # the block runs inside the layer scan, under the segment's scope
    assert any(re.search(r"/layers/while/body/.*kv_write/", n)
               for n in names)


@pytest.mark.parametrize("arch,scope", [("qwen3-moe-30b-a3b", "moe"),
                                        ("xlstm-125m", "ssm"),
                                        ("llama-3.2-vision-90b",
                                         "cross_attn")])
def test_other_block_kinds_carry_their_mixer_scope(arch, scope):
    found = {_innermost(n)
             for n in _op_names(REGISTRY[arch].reduced(), "decode")}
    assert scope in found


def _serve(model, params, prompts):
    """Serve ``prompts`` in batches of ``B``; the tokens of each."""
    eng = ServeEngine(model, params, smax=SMAX)
    rids = [eng.submit(p, max_new=NEW) for p in prompts]
    out = eng.run(batch_size=B)
    return [out[r] for r in rids]


def _span_events(log_dir):
    """The ``serve.*`` events of the thread that holds them, in time
    order, as (start, end, name, args)."""
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = [(e.start_ns, e.end_ns, e.name, dict(e.stats))
                   for e in line.events if e.name.startswith("serve.")]
            if evs:
                return sorted(evs, key=lambda ev: (ev[0], -ev[1]))
    return []


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    cfg = _dense2()
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab_size, S, dtype=np.int32)
               for _ in range(2 * B)]
    plain = _serve(model, params, prompts)
    log_dir = tmp_path_factory.mktemp("trace")
    calls = []
    with jax.profiler.trace(str(log_dir)):
        eng = ServeEngine(model, params, smax=SMAX)
        decode = eng.decode_fn
        eng.decode_fn = lambda *a: calls.append(1) or decode(*a)
        rids = [eng.submit(p, max_new=NEW) for p in prompts]
        out = eng.run(batch_size=B)
    return {"plain": plain, "traced": [out[r] for r in rids],
            "events": _span_events(log_dir), "decode_calls": len(calls)}


def test_tokens_with_the_profiler_on_equal_those_with_it_off(traced):
    assert traced["traced"] == traced["plain"]
    assert all(len(t) == NEW for t in traced["traced"])


def test_each_span_carries_its_arguments(traced):
    evs = traced["events"]
    keys = {"serve.batch": {"batch", "rows", "prompt_len", "host_reads"},
            "serve.prefill": {"batch", "rows", "prompt_len"},
            "serve.sample": {"batch", "step"},
            "serve.schedule": {"batch", "step", "live", "evicted"},
            "serve.decode": {"batch", "step", "pos"}}
    assert {name for _, _, name, _ in evs} == set(keys)
    for _, _, name, args in evs:
        assert set(args) == keys[name], name
    batches = [a for _, _, n, a in evs if n == "serve.batch"]
    assert [a["batch"] for a in batches] == [1, 2]
    assert all(a["rows"] == B and a["prompt_len"] == S for a in batches)


def test_spans_nest_in_their_batch_and_follow_the_steps(traced):
    evs = traced["events"]
    batches = {a["batch"]: (s, e) for s, e, n, a in evs
               if n == "serve.batch"}
    phases = [(s, e, n, a) for s, e, n, a in evs if n in PHASES]
    for s, e, n, a in phases:
        lo, hi = batches[a["batch"]]
        assert lo <= s and e <= hi, n
    # the phases of the engine do not overlap one another
    assert all(e <= s2 for (_, e, *_), (s2, *_) in zip(phases, phases[1:]))
    for b in batches:
        seq = [(n, a) for _, _, n, a in phases if a["batch"] == b]
        # prefill, then (sample, schedule) per step with a decode between
        assert [n for n, _ in seq] == (
            ["serve.prefill"]
            + ["serve.sample", "serve.schedule", "serve.decode"] * (NEW - 1)
            + ["serve.sample", "serve.schedule"])
        decodes = [a for n, a in seq if n == "serve.decode"]
        assert [a["step"] for a in decodes] == list(range(1, NEW))
        assert [a["pos"] for a in decodes] == [S + k for k in range(NEW - 1)]
        sched = [a for n, a in seq if n == "serve.schedule"]
        assert [a["live"] for a in sched] == [B] * (NEW - 1) + [0]
        assert all(a["evicted"] == 0 for a in sched)
    # one serve.decode per decode step the engine ran
    n_decode = sum(1 for _, _, n, _ in evs if n == "serve.decode")
    assert n_decode == traced["decode_calls"] == 2 * (NEW - 1)


class _NextToken:
    """Jax-free stand-in model: next token = last token + 1."""

    V = 16

    def _onehot(self, idx):
        out = np.zeros((len(idx), self.V), np.float32)
        out[np.arange(len(idx)), idx % self.V] = 1.0
        return out

    def prefill(self, params, batch, smax):
        return self._onehot(np.asarray(batch["tokens"])[:, -1] + 1), None

    def decode_step(self, params, cache, tokens):
        return self._onehot(np.asarray(tokens)[:, 0] + 1), cache


def test_schedule_span_counts_evictions(tmp_path):
    eng = ServeEngine(_NextToken(), params=None, smax=8, jit=False,
                      max_retries=0)
    eng.submit(np.array([3], np.int32), max_new=4)
    eng.submit(np.array([7], np.int32), max_new=4, deadline_steps=2)
    with jax.profiler.trace(str(tmp_path)):
        eng.run(batch_size=2)
    sched = [a for _, _, n, a in _span_events(tmp_path)
             if n == "serve.schedule"]
    assert [(a["step"], a["live"], a["evicted"]) for a in sched] == [
        (0, 2, 0), (1, 1, 1), (2, 1, 0), (3, 0, 0)]
