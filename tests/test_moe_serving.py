"""The dropless sparse MoE layer on the serving path (``models/moe.moe_sparse``)
and Qwen3-MoE served through ``ServeEngine``, on the CPU.

The layer is checked against the dense oracle ``moe_dense`` in float32 on
routings that stress the grouped product: all tokens on one expert,
experts with no rows, row counts that fill no tile, a single decode
token, and expert weights read in place from a stack over layers. The
model is checked, prefill then decode through the engine's own step
functions, against the benchmark's plain float32 reference
(``bench/configs/qwen3-moe-30b-a3b.ref.py``), and its routing counters
against a count made on the host from the reference's routing.
"""
import dataclasses
import importlib.util
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.models import build_model
from repro.models.moe import expert_counts, moe_dense, moe_sparse, router_topk
from repro.serve.engine import ServeEngine

ROOT = Path(__file__).resolve().parents[1]
REF = ROOT / "bench" / "configs" / "qwen3-moe-30b-a3b.ref.py"


def _weights(rng, T, d, f, E, lead=()):
    def n(*shape, scale=1.0):
        return jnp.asarray(rng.standard_normal(shape) * scale, jnp.float32)

    return (n(T, d), n(d, E), n(*lead, E, d, f, scale=d ** -0.5),
            n(*lead, E, d, f, scale=d ** -0.5),
            n(*lead, E, f, d, scale=f ** -0.5))


def _one_expert_router(x, wr):
    """A router under which every token's first choice is expert 3."""
    x = jnp.abs(x)
    return x, wr.at[:, 3].set(10.0)


@pytest.mark.parametrize("T,k,force", [
    (40, 2, True),      # every token sends a row to expert 3
    (24, 1, True),      # one expert holds every row, fifteen hold none
    (3, 2, False),      # 6 rows over 16 experts: most get none
    (37, 3, False),     # 111 rows: a multiple of no row tile
    (1, 4, False),      # a single decode token
], ids=["uneven", "one_expert_only", "idle_experts", "ragged_rows",
        "single_token"])
def test_sparse_matches_dense(T, k, force):
    rng = np.random.default_rng(T * 10 + k)
    x, wr, wg, wu, wd = _weights(rng, T, 64, 96, 16)
    if force:
        x, wr = _one_expert_router(x, wr)
    y, counts = jax.jit(lambda *a: moe_sparse(*a, k=k))(x, wr, wg, wu, wd)
    want = moe_dense(x, wr, wg, wu, wd, k=k)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                               rtol=2e-5, atol=2e-5)
    ids = np.asarray(router_topk(x, wr, k)[1])
    sizes = np.bincount(ids.reshape(-1), minlength=16)
    assert counts.tolist() == [T * k, int((sizes > 0).sum()),
                               int(sizes.max())]
    assert expert_counts(jnp.asarray(ids), 16).tolist() == counts.tolist()
    if force:
        assert sizes[3] == T


def test_sparse_reads_one_layer_of_a_stack():
    """Expert weights stacked over layers, read in place at ``layer``, give
    that layer's result."""
    rng = np.random.default_rng(7)
    x, wr, wg, wu, wd = _weights(rng, 19, 64, 96, 16, lead=(3,))
    f = jax.jit(lambda x, wr, a, b, c, i: moe_sparse(x, wr, a, b, c, k=4,
                                                     layer=i))
    for i in range(3):
        y, _ = f(x, wr, wg, wu, wd, jnp.int32(i))
        want = moe_dense(x, wr, wg[i], wu[i], wd[i], k=4)
        np.testing.assert_allclose(np.asarray(y), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


# -- Qwen3-MoE through the engine, against the plain reference ------------

B, P, NEW = 3, 24, 5
SMALL = dict(n_layers=4, d_model=256, n_heads=4, n_kv_heads=2, head_dim=64,
             d_ff=128, n_experts=16, experts_per_token=4, vocab_size=2048)


@pytest.fixture(scope="module")
def served():
    cfg = dataclasses.replace(REGISTRY["qwen3-moe-30b-a3b"], **SMALL)
    model = build_model(cfg, remat=False)
    params = model.init(jax.random.PRNGKey(3), jnp.float32)
    # norm scales away from one, so that a norm read wrongly shows
    keys = iter(jax.random.split(jax.random.PRNGKey(4), 8))
    seg = params["segments"][0]
    for name in ("ln1", "ln2", "q_norm", "k_norm"):
        seg[name] = 1.0 + 0.1 * jax.random.normal(next(keys), seg[name].shape)
    rng = np.random.default_rng(5)
    prompts = [rng.integers(0, cfg.vocab_size, P, dtype=np.int32)
               for _ in range(B)]
    eng = ServeEngine(model, params, smax=P + NEW)
    logits, caches = [], []
    prefill, decode = eng.prefill_fn, eng.decode_fn

    def keep(out):
        logits.append(np.asarray(out[0]))
        caches.append(out[1])
        return out

    eng.prefill_fn = lambda *a: keep(prefill(*a))
    eng.decode_fn = lambda p, c, t: keep(decode(p, c, t))
    rids = [eng.submit(p, max_new=NEW) for p in prompts]
    out = eng.run(batch_size=B)
    toks = np.stack([out[r] for r in rids])
    spec = importlib.util.spec_from_file_location("qwen3_moe_ref", REF)
    ref = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(ref)
    hf = {"num_hidden_layers": cfg.n_layers, "rms_norm_eps": cfg.norm_eps,
          "rope_theta": cfg.rope_theta, "tie_word_embeddings": False,
          "num_experts_per_tok": cfg.experts_per_token}
    seq = np.concatenate([np.stack(prompts), toks[:, :-1]], axis=1)
    return {"model": model, "params": params, "ref": ref, "hf": hf,
            "seq": seq, "logits": np.stack(logits, 1), "cache": caches[-1],
            "cfg": cfg}


def test_prefill_then_decode_match_the_reference(served):
    """Logits of prefill and of each decode step, through the engine in
    float32, against the reference's full forward over the same tokens."""
    want = served["ref"].logits(served["hf"], served["params"],
                                served["seq"], P - 1)
    got = served["logits"][:, :, :served["cfg"].vocab_size]
    assert got.shape == want.shape == (B, NEW, SMALL["vocab_size"])
    np.testing.assert_allclose(got, np.asarray(want), rtol=1e-4, atol=1e-4)


def test_counters_match_a_host_count(served):
    """The counters the decode state carries, per layer, against a count
    on the host of the reference's routing of the same tokens: prefill's
    from the prompt, the decode steps' summed over steps (the largest
    group: the largest)."""
    chosen, _ = served["ref"].routes(served["hf"], served["params"],
                                     served["seq"])
    sizes = np.asarray(chosen).sum(1)             # [L, S, E] over the batch
    prompt = np.asarray(chosen)[:, :, :P].sum((1, 2))     # [L, E]
    want = np.zeros((SMALL["n_layers"], 2, 3), np.int64)
    want[:, 0] = np.stack([prompt.sum(-1), (prompt > 0).sum(-1),
                           prompt.max(-1)], -1)
    steps = sizes[:, P:P + NEW - 1]                       # decode steps
    want[:, 1] = np.stack([steps.sum((1, 2)), (steps > 0).sum((1, 2)),
                           steps.max((1, 2))], -1)
    got = np.asarray(served["cache"]["segments"][0]["moe"])
    np.testing.assert_array_equal(got, want)
    totals = served["model"].moe_counters(served["cache"])
    assert totals == {
        "moe_prefill_rows": int(want[:, 0, 0].sum()),
        "moe_prefill_experts": int(want[:, 0, 1].sum()),
        "moe_prefill_largest": int(want[:, 0, 2].max()),
        "moe_decode_rows": int(want[:, 1, 0].sum()),
        "moe_decode_experts": int(want[:, 1, 1].sum()),
        "moe_decode_largest": int(want[:, 1, 2].max())}
    assert totals["moe_prefill_rows"] == (SMALL["n_layers"] * B * P
                                          * SMALL["experts_per_token"])


def test_dense_model_carries_no_counter():
    model = build_model(REGISTRY["qwen2-1.5b"].reduced(), remat=False)
    cache = model.abstract_cache(2, 16)
    assert all("moe" not in c for c in cache["segments"])
    assert model.moe_counters({"segments": [{}]}) == {}


def test_engine_puts_counters_on_the_batch_span(served, tmp_path):
    """One fetch per batch, as arguments of ``serve.batch``."""
    from jax.profiler import ProfileData

    model, params = served["model"], served["params"]
    eng = ServeEngine(model, params, smax=P + NEW)
    with jax.profiler.trace(str(tmp_path)):
        for p in served["seq"][:, :P]:
            eng.submit(p, max_new=NEW)
        eng.run(batch_size=B)
    (path,) = tmp_path.glob("**/*.xplane.pb")
    batches = [dict(e.stats) for pl in ProfileData.from_file(str(path)).planes
               for ln in pl.lines for e in ln.events
               if e.name == "serve.batch"]
    assert len(batches) == 1
    args = batches[0]
    want = model.moe_counters(served["cache"])
    assert {k: int(args[k]) for k in want} == want
