"""The MoE cell's operation and byte counts (``bench/flops_moe.py``) against
hand counts, and its ``correct`` against faults.

The correctness tests drive the rest of a run of ``qwen3-moe-30b-a3b.decode``
(set-up, window, reference comparison, judgement against the cell's own
limits), skipping only the look for a chip, at a size the CPU holds:
Qwen3-MoE's layout at hidden size 256, 4 layers, 16 experts of width 128
with 4 per token, a 2,048-token vocabulary, 64-token prompts and 16 new
tokens. A sound run must come out correct; an altered token, a cache left
unchanged and the float8 control put in the program's place must not.
"""
import json
from pathlib import Path

import pytest

from bench import flops_moe
from bench.run import Cell, judge, run

BENCH = Path(__file__).resolve().parents[1]
CONFIG = BENCH / "configs" / "qwen3-moe-30b-a3b.json"
LIMITS = json.loads((BENCH / "limits" / "qwen3-moe-30b-a3b.decode.json")
                    .read_text())["limits"]
SPEC = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
FULL = json.loads(CONFIG.read_text())
SMALL = dict(FULL, hidden_size=256, moe_intermediate_size=128,
             num_hidden_layers=4, num_attention_heads=4,
             num_key_value_heads=2, head_dim=64, num_experts=16,
             num_experts_per_tok=4, vocab_size=2048)
TRAFFIC = {"prompt_tokens": 64, "new_tokens": 16, "batch": 4,
           "rate_per_s": 1000.0, "sample_requests": 4}
SEED = 2 ** 31 + 12345

# d 4, 2 layers, 2 query heads and 1 key/value head of 2, 4 experts of
# width 3 with 2 per token, vocabulary 10, untied
TINY = {"hidden_size": 4, "num_hidden_layers": 2, "num_attention_heads": 2,
        "num_key_value_heads": 1, "head_dim": 2, "moe_intermediate_size": 3,
        "num_experts": 4, "num_experts_per_tok": 2, "vocab_size": 10,
        "tie_word_embeddings": False}


def test_counts_by_hand():
    # one layer outside its experts: q 16, k 8, v 8, o 16, router 16 = 64
    # matrix parameters; one expert 3 * 4 * 3 = 36 (72 bytes)
    assert flops_moe.expert_weight_bytes(TINY) == 72
    # 12 rows: 6 * 4 * 3 operations each; their weights and rows in, out
    assert flops_moe.expert_flops(TINY, 12) == 864
    assert flops_moe.expert_bytes(TINY, 12, 5) == 5 * 72 + 12 * 2 * 4 * 2
    # batch 3 at ctx 5: 2 layers of (2 * 64 * 3 + scores and PV
    # 4 * 2 * 2 * 3 * 5), experts of 2 * 3 * 2 rows, head 2 * 4 * 10 * 3
    assert flops_moe.decode_flops(TINY, 3, 5) == 2 * (384 + 240) + 864 + 240
    # one prompt of 3: lower triangle 4 * 2 * 2 * 3 * 4 / 2, the head at
    # the last position only
    assert flops_moe.prefill_flops(TINY, 1, 3) == (2 * (384 + 96) + 864
                                                    + 80)
    # fixed weights 2 * (64 + norms 2 * 4 + q/k norms 2 * 2) + 3 embedding
    # rows of 4 + final norm 4 + head 40, in bytes; 5 experts; the cache
    # 16 bytes a position (2 layers x k, v x 2 x 2 bytes), 4 read and 1
    # written per row
    fixed = (2 * 76 + 12 + 4 + 40) * 2
    assert flops_moe.decode_bytes(TINY, 3, 5, 5) == (fixed + 360
                                                     + 3 * 4 * 16 + 3 * 16)
    assert flops_moe.prefill_bytes(TINY, 1, 3, 8) == fixed + 576 + 3 * 16


def test_decode_step_bytes_at_the_cell():
    """The cell's decode step: 112 of 128 experts touched in each of 8
    layers (the expected share at 256 rows) read 8.46 GB of 9.7 GB."""
    experts = 8 * 112
    assert flops_moe.expert_weight_bytes(FULL) == 3 * 2048 * 768 * 2
    total = flops_moe.decode_bytes(FULL, 32, 640, experts)
    assert 9.6e9 < total < 9.8e9
    share = experts * flops_moe.expert_weight_bytes(FULL) / total
    assert 0.86 < share < 0.88


def small_cell():
    return Cell("qwen3-moe-30b-a3b.decode", "qwen3-moe-30b-a3b", CONFIG,
                SMALL, TRAFFIC, 1, LIMITS)


def drive():
    # a window of 0 s serves exactly one batch: the sample is all of it
    return run(small_cell(), SPEC, SEED, 0.0, False,
               {"platform": "cpu", "kind": "cpu", "count": 1}, {})[0]


def test_sound_run_is_correct():
    res = drive()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["checks"]) == set(LIMITS)


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


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged],
                         ids=["token_altered", "state_unchanged"])
def test_fault_is_not_correct(monkeypatch, fault):
    from repro.serve import engine

    monkeypatch.setattr(engine, "make_decode_fn",
                        fault(engine.make_decode_fn))
    res = drive()
    assert not res["correct"], res["checks"]


def test_control_is_not_correct():
    """The reference in float8 in the program's place: at the served
    positions of a sound run, the tokens it puts first lie further below
    the float32 reference's best than the limits allow."""
    import jax

    from bench.drivers import serve_lm, serve_moe

    tr = serve_lm.Traffic.from_json(TRAFFIC)
    eng, _ = serve_moe.setup(small_cell(), SEED, trace=False)
    w = serve_lm.serve_window(eng, tr, SEED, 0.0, SMALL["vocab_size"])
    rows = serve_lm.sample_rows(w.finished, tr.sample_requests, SEED)
    ref = serve_lm.load_reference(CONFIG)
    gap, gap_ctrl = serve_lm.served_gaps(ref, SMALL, eng.params, rows,
                                         control=True)
    mean, mean_ctrl = serve_moe.mean_gaps(ref, SMALL, eng.params, rows,
                                          control=True)
    assert judge({"max_logit_gap": gap, "mean_logit_gap": mean}, LIMITS)[0]
    assert not judge({"max_logit_gap": gap_ctrl,
                      "mean_logit_gap": mean_ctrl}, LIMITS)[0]
    del eng
    jax.clear_caches()
