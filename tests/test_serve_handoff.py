"""The engine's device-side token handoff: greedy tokens go from one step
to the next on the device, scheduling reads only counts, and the host
reads a batch's tokens once, after its last decode step."""
import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs import REGISTRY
from repro.models import build_model
from repro.serve.engine import ServeEngine, make_decode_fn, make_prefill_fn

B, S, SMAX, NEW = 2, 8, 16, 4


@pytest.fixture(scope="module")
def dense():
    cfg = dataclasses.replace(REGISTRY["qwen2-1.5b"].reduced(), n_layers=2)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(0), jnp.float32)
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, cfg.vocab_size, S, dtype=np.int32)
               for _ in range(2 * B)]
    return model, params, prompts


def _plain_greedy(model, params, prompts, rules=None, smax=SMAX):
    """Greedy decoding one batch at a time: argmax, read to the host, feed
    the tokens back."""
    prefill = jax.jit(make_prefill_fn(model, rules, smax))
    decode = jax.jit(make_decode_fn(model, rules))
    out = []
    for i in range(0, len(prompts), B):
        logits, cache = prefill(params,
                                {"tokens": jnp.asarray(prompts[i:i + B])})
        toks = []
        for step in range(NEW):
            tok = np.asarray(jnp.argmax(logits, -1), np.int32)
            toks.append(tok)
            if step < NEW - 1:
                logits, cache = decode(params, cache,
                                       jnp.asarray(tok)[:, None])
        out.extend(np.stack(toks, 1).tolist())
    return out


def test_served_tokens_equal_a_plain_greedy_loop(dense):
    model, params, prompts = dense
    eng = ServeEngine(model, params, smax=SMAX)
    rids = [eng.submit(p, max_new=NEW) for p in prompts]
    out = eng.run(batch_size=B)
    assert [out[r] for r in rids] == _plain_greedy(model, params, prompts)
    assert all(type(t) is int for r in rids for t in out[r])


@pytest.mark.parametrize("arch,prompt,smax", [("qwen2-1.5b", S, SMAX),
                                               ("hymba-1.5b", 20, 32)])
def test_kernel_serves_the_tokens_of_the_einsum(arch, prompt, smax):
    """Without sharding rules, decode attention is the flash-decoding
    kernel (interpreted here); under rules (one device) it is the einsum.
    Both serve the same tokens. Hymba's windowed layer keeps a ring of 8
    sink and 16 window slots, which its 28 prompt positions (meta tokens
    included) have already wrapped; its global layer's 40 slots take one
    block."""
    from repro.distributed.sharding import rules_for
    from repro.launch.mesh import single_device_mesh

    cfg = dataclasses.replace(REGISTRY[arch].reduced(), n_layers=2)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(2), jnp.float32)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, cfg.vocab_size, prompt, dtype=np.int32)
               for _ in range(2 * B)]
    cache = model.abstract_cache(B, smax, jnp.float32)
    tokens = jax.ShapeDtypeStruct((B, 1), jnp.int32)
    rules = rules_for(single_device_mesh(), n_heads=cfg.n_heads,
                      d_ff=cfg.d_ff)
    for r, kernel in ((None, True), (rules, False)):
        jaxpr = str(jax.make_jaxpr(make_decode_fn(model, r))(
            params, cache, tokens))
        assert ("pallas_call" in jaxpr) == kernel
    eng = ServeEngine(model, params, smax=smax)
    rids = [eng.submit(p, max_new=NEW) for p in prompts]
    out = eng.run(batch_size=B)
    assert [out[r] for r in rids] == _plain_greedy(model, params, prompts,
                                                   rules, smax)


def test_no_token_reaches_the_host_before_the_last_decode(dense):
    model, params, prompts = dense
    eng = ServeEngine(model, params, smax=SMAX)
    rids = [eng.submit(p, max_new=NEW) for p in prompts]
    reqs = list(eng.queue)
    seen = []
    decode = eng.decode_fn

    def watch(p, cache, tokens):
        seen.append(([len(r.generated) for r in reqs],
                     isinstance(tokens, jax.Array)))
        return decode(p, cache, tokens)

    eng.decode_fn = watch
    out = eng.run(batch_size=B)
    per_batch = NEW - 1
    assert len(seen) == 2 * per_batch
    # while the first batch runs nothing is appended; while the second
    # runs, only the first batch's rows hold their tokens
    assert [g for g, _ in seen] == ([[0] * 2 * B] * per_batch
                                    + [[NEW] * B + [0] * B] * per_batch)
    # each step is fed the device array the argmax gave
    assert all(on_device for _, on_device in seen)
    assert all(len(out[r]) == NEW for r in rids)


class _NextToken:
    """Jax-free stand-in model: next token = last token + 1 (mod V)."""

    V = 16

    def _onehot(self, idx):
        out = np.zeros((len(idx), self.V), np.float32)
        out[np.arange(len(idx)), idx % self.V] = 1.0
        return out

    def prefill(self, params, batch, smax):
        return self._onehot(np.asarray(batch["tokens"])[:, -1] + 1), None

    def decode_step(self, params, cache, tokens):
        return self._onehot(np.asarray(tokens)[:, 0] + 1), cache


class _WithCounters(_NextToken):
    """The stand-in with routing counters, which cost a read of their own."""

    @staticmethod
    def moe_counters(cache):
        return {"moe_decode_rows": 1}


def test_evicted_and_requeued_rows_keep_their_tokens():
    eng = ServeEngine(_NextToken(), params=None, smax=8, jit=False,
                      max_retries=1)
    r1 = eng.submit(np.array([3], np.int32), max_new=4)
    r2 = eng.submit(np.array([7], np.int32), max_new=4, deadline_steps=2)
    r3 = eng.submit(np.array([11], np.int32), max_new=3, deadline_steps=1)
    out = eng.run(batch_size=2)
    # batch 1 (r1, r2): r2 is evicted after two steps and re-queued behind
    # r3; batch 2 (r3, r2): r3 is evicted at once and re-queued, r2 starts
    # again from its prompt and finishes its four tokens; batch 3 (r3):
    # evicted for good with what it generated
    assert out == {r1: [4, 5, 6, 7], r2: [8, 9, 8, 9], r3: [12, 12]}
    assert eng.evicted == [r3]
    assert list(eng.evicted_partial) == [r3]
    assert eng.evicted_partial[r3].generated == [12, 12]
    assert eng.evicted_partial[r3].retries == 1
    assert sorted(eng.completed) == [r1, r2]


def _batch_args(log_dir):
    (path,) = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    return [dict(e.stats) for pl in ProfileData.from_file(path).planes
            for ln in pl.lines for e in ln.events if e.name == "serve.batch"]


@pytest.mark.parametrize("kind,reads", [("dense", 1), ("counters", 2)])
def test_batch_span_counts_its_host_reads(kind, reads, dense, tmp_path):
    if kind == "dense":
        model, params, prompts = dense
        eng = ServeEngine(model, params, smax=SMAX)
    else:
        eng = ServeEngine(_WithCounters(), params=None, smax=8, jit=False)
        prompts = [np.array([i], np.int32) for i in range(2 * B)]
    with jax.profiler.trace(str(tmp_path)):
        for p in prompts:
            eng.submit(p, max_new=NEW)
        eng.run(batch_size=B)
    args = _batch_args(tmp_path)
    assert len(args) == 2
    assert all(int(a["host_reads"]) == reads for a in args)
