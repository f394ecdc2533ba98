"""Per-arch train smoke test: reduced configs of all 10 assigned
architectures run two full train steps on CPU (rematerialised forward,
gradient, optimizer update); loss and gradient norm asserted finite, and
the second step's loss differs from the first's. Kept apart from
``test_models_smoke.py`` so the two files run on separate workers."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import REGISTRY
from repro.models import build_model
from repro.train.loop import init_state, make_train_step
from test_models_smoke import ARCHS, _batch

# whole-module: every case compiles + runs a real train step (5-65s each)
pytestmark = pytest.mark.slow


@pytest.mark.parametrize("arch", ARCHS)
def test_train_step_no_nans(arch):
    cfg = REGISTRY[arch].reduced()
    model = build_model(cfg, remat=True)
    state = init_state(model, jax.random.PRNGKey(0), dtype=jnp.float32)
    step_fn = make_train_step(model, None,
                              lr_schedule=lambda s: jnp.asarray(1e-3))
    batch = _batch(cfg)
    state, metrics = step_fn(state, batch)
    assert np.isfinite(float(metrics["loss"]))
    assert np.isfinite(float(metrics["grad_norm"]))
    assert float(metrics["grad_norm"]) > 0
    assert int(state["opt"]["step"]) == 1
    # a second step changes the loss (params actually updated)
    _, m2 = step_fn(state, batch)
    assert float(m2["loss"]) != float(metrics["loss"])
