"""``correct`` comes out false when the timed path is broken underneath.

Each test drives the rest of a run of the decode cell (set-up, window,
reference comparison, judgement against the cell's own limit), skipping
only the look for a chip, at a size the CPU holds: qwen2-1.5b's layout
at hidden size 256, 4 layers, a 2,048-token vocabulary, 64-token prompts
and 16 new tokens. (At hidden size 64 and 2 layers the random model's
next token hardly depends on the context, and a cache left unchanged
went unseen on some samples.) The faults are planted in the program's serving
functions, which ``ServeEngine`` builds from ``make_prefill_fn`` and
``make_decode_fn``. A sound run must come out correct; each fault, and
the float8 control put in the program's place, must not.
"""
import json
from pathlib import Path

import pytest

from bench.run import Cell, judge, run

BENCH = Path(__file__).resolve().parents[1]
CONFIG = BENCH / "configs" / "qwen2-1.5b.json"
LIMITS = json.loads((BENCH / "limits" / "qwen2-1.5b.decode.json")
                    .read_text())["limits"]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
SMALL = dict(json.loads(CONFIG.read_text()), hidden_size=256,
             intermediate_size=512, num_hidden_layers=4,
             num_attention_heads=4, num_key_value_heads=2, vocab_size=2048)
TRAFFIC = {"prompt_tokens": 64, "new_tokens": 16, "batch": 4,
           "rate_per_s": 1000.0, "sample_requests": 4}
SEED = 2 ** 31 + 12345


def small_cell(config=SMALL, traffic=TRAFFIC):
    return Cell("qwen2-1.5b.decode", "qwen2-1.5b", CONFIG, config, traffic,
                1, LIMITS)


def drive(cell=None):
    # a window of 0 s serves exactly one batch: the sample is all of it
    return run(cell or small_cell(), SPEC, SEED, 0.0, False,
               {"platform": "cpu", "kind": "cpu", "count": 1}, {})[0]


def test_sound_run_is_correct():
    res = drive()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"


def _token_altered(orig):
    def factory(model, rules):
        f = orig(model, rules)

        def decode(params, cache, tokens):
            import jax.numpy as jnp

            logits, cache = f(params, cache, tokens)
            return jnp.roll(logits, 1, axis=-1), cache
        return decode
    return factory


def _state_unchanged(orig):
    def factory(model, rules):
        f = orig(model, rules)

        def decode(params, cache, tokens):
            logits, _ = f(params, cache, tokens)
            return logits, cache
        return decode
    return factory


def _half_batch(orig):
    def factory(model, rules, smax):
        f = orig(model, rules, smax)

        def prefill(params, batch):
            import jax
            import jax.numpy as jnp

            toks = batch["tokens"]
            B = toks.shape[0]
            h = max(1, B // 2)
            logits, cache = f(params, {"tokens": toks[:h]})

            def fill(a):
                return a if a.ndim < 2 else jnp.concatenate(
                    [a, a[:, :B - h]], axis=1)
            return (jnp.concatenate([logits, logits[:B - h]]),
                    jax.tree_util.tree_map(fill, cache))
        return prefill
    return factory


@pytest.mark.parametrize("name,fault", [
    ("make_decode_fn", _token_altered),
    ("make_decode_fn", _state_unchanged),
    ("make_prefill_fn", _half_batch),
], ids=["token_altered", "state_unchanged", "half_batch_left_out"])
def test_fault_is_not_correct(monkeypatch, name, fault):
    from repro.serve import engine

    monkeypatch.setattr(engine, name, fault(getattr(engine, name)))
    res = drive()
    assert not res["correct"], res["checks"]


def test_control_is_not_correct():
    """The reference in float8 in the program's place: at the served
    positions of a sound run, the tokens it puts first lie further below
    the float32 reference's best than the limit allows."""
    import jax

    from bench.drivers import serve_lm

    tr = serve_lm.Traffic.from_json(TRAFFIC)
    eng, _ = serve_lm.setup(small_cell(), SEED, trace=False)
    w = serve_lm.serve_window(eng, tr, SEED, 0.0, SMALL["vocab_size"])
    rows = serve_lm.sample_rows(w.finished, tr.sample_requests, SEED)
    ref = serve_lm.load_reference(CONFIG)
    prog, ctrl = serve_lm.served_gaps(ref, SMALL, eng.params, rows,
                                      control=True)
    assert judge({"max_logit_gap": prog}, LIMITS)[0], prog
    assert not judge({"max_logit_gap": ctrl}, LIMITS)[0], ctrl
    del eng
    jax.clear_caches()
