"""``decode_attn_share``: the decode program's op time under the
``attn_cache`` scope, and ``None`` from a program whose vocabulary lacks
that scope (every commit before the scope was added)."""
import importlib.util
from pathlib import Path

import pytest

from bench.metrics import _scopes
from bench.metrics._scopes import ScopeSummary

READER = Path(__file__).resolve().parents[1] / "metrics" / "decode_attn_share.py"


def _reader():
    spec = importlib.util.spec_from_file_location("decode_attn_share", READER)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def window(monkeypatch):
    s = ScopeSummary(window_s=1.0, executions={"jit_decode": 4},
                     op_s={("jit_decode", "attn_cache"): 0.15,
                           ("jit_decode", "mlp"): 0.6,
                           ("jit_decode", "attn"): 0.25,
                           ("jit_prefill", "attn"): 0.5})
    monkeypatch.setattr(_scopes, "window_summary", lambda: s)


def test_reads_the_share_of_the_decode_program(window):
    assert _reader().read(None) == pytest.approx(15.0)


def test_a_program_without_the_scope_reads_none(window, monkeypatch):
    mod = _reader()
    monkeypatch.setattr(mod, "SCOPES", tuple(s for s in mod.SCOPES
                                             if s != "attn_cache"))
    assert mod.read(None) is None
