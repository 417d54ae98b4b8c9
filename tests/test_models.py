"""Per-arch smoke tests: reduced config forward/train-step/decode, no NaNs."""
import numpy as np
import pytest
import jax
import jax.numpy as jnp

# Each per-arch case compiles a reduced model (4-12 s each); the sweep
# dominates suite wall time, so the whole module runs in the slow tier.
pytestmark = pytest.mark.slow

from repro.configs import ARCHS, get_config
from repro.models import model as M
from repro.optim.adamw import AdamWConfig, adamw_init
from repro.train.train_loop import make_train_step


def _batch(cfg, b=2, s=16, seed=0):
    key = jax.random.PRNGKey(seed)
    batch = {
        "tokens": jax.random.randint(key, (b, s), 0, cfg.vocab),
        "labels": jax.random.randint(jax.random.fold_in(key, 1), (b, s), 0, cfg.vocab),
    }
    if cfg.frontend:
        batch["embeds"] = jax.random.normal(
            jax.random.fold_in(key, 2), (b, cfg.frontend_len, cfg.d_model), jnp.float32
        )
    return batch


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_forward_shapes_and_finite(arch):
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg)
    logits = M.forward(cfg, params, batch)
    assert logits.shape == (2, 16, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_init_params_jit_matches_eager(arch):
    """init_params runs under jit (only the stacked weights materialise);
    it draws bit for bit what the same code draws eagerly."""
    cfg = get_config(arch).reduced()
    got = M.init_params(cfg, jax.random.PRNGKey(0))
    with jax.disable_jit():
        want = M.init_params(cfg, jax.random.PRNGKey(0))
    assert jax.tree_util.tree_structure(got) == jax.tree_util.tree_structure(want)
    for g, w in zip(jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)):
        assert g.dtype == w.dtype and np.array_equal(np.asarray(g), np.asarray(w))


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_train_step_reduces_loss_and_stays_finite(arch):
    cfg = get_config(arch).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    step = jax.jit(make_train_step(cfg, AdamWConfig(lr=5e-3, warmup_steps=1, total_steps=10)))
    batch = _batch(cfg, b=2, s=16)
    losses = []
    for _ in range(3):
        params, opt, metrics = step(params, opt, batch)
        losses.append(float(metrics["loss"]))
        assert np.isfinite(losses[-1])
        assert not bool(metrics["skipped"])
    assert losses[-1] < losses[0]  # same batch: loss must drop


@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_decode_matches_forward(arch):
    """Stepwise decode must reproduce the teacher-forced forward logits."""
    cfg = get_config(arch).reduced()
    if cfg.family == "vlm":
        pytest.skip("vlm decode operates post-prefill with image prefix")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    b, s = 2, 8
    batch = _batch(cfg, b=b, s=s)
    ref = M.forward(cfg, params, batch)
    state = M.init_decode_state(cfg, b, 32, ring=False)
    if cfg.family == "audio":
        state["memory"] = M.encode(cfg, params, batch["embeds"])
    outs = []
    for t in range(s):
        logits, state = M.decode_step(cfg, params, state, batch["tokens"][:, t : t + 1])
        outs.append(logits[:, 0])
    got = jnp.stack(outs, axis=1)
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), rtol=2e-3, atol=2e-3
    )


def test_swa_ring_buffer_decode_matches_full_cache():
    """SWA ring cache (window-bounded) must equal a full-length cache."""
    cfg = get_config("h2o-danube-3-4b").reduced()
    assert cfg.window is not None
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    b, steps = 1, 24  # well past the reduced window... window=64 reduced
    toks = jax.random.randint(jax.random.PRNGKey(2), (b, steps), 0, cfg.vocab)
    sr = M.init_decode_state(cfg, b, cfg.window, ring=True)
    sf = M.init_decode_state(cfg, b, 64, ring=False)
    for t in range(steps):
        lr_, sr = M.decode_step(cfg, params, sr, toks[:, t : t + 1])
        lf_, sf = M.decode_step(cfg, params, sf, toks[:, t : t + 1])
        np.testing.assert_allclose(
            np.asarray(lr_, np.float32), np.asarray(lf_, np.float32),
            rtol=2e-3, atol=2e-3,
        )


def test_prefill_then_decode_equals_stepwise():
    cfg = get_config("minicpm-2b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    toks = jax.random.randint(jax.random.PRNGKey(1), (1, 10), 0, cfg.vocab)
    # multi-token prefill of the first 6, then 4 decode steps
    s1 = M.init_decode_state(cfg, 1, 32, ring=False)
    lg, s1 = M.decode_step(cfg, params, s1, toks[:, :6])
    outs = [lg[:, -1]]
    for t in range(6, 10):
        lg, s1 = M.decode_step(cfg, params, s1, toks[:, t : t + 1])
        outs.append(lg[:, 0])
    # stepwise from scratch
    s2 = M.init_decode_state(cfg, 1, 32, ring=False)
    outs2 = []
    for t in range(10):
        lg2, s2 = M.decode_step(cfg, params, s2, toks[:, t : t + 1])
        outs2.append(lg2[:, 0])
    got = jnp.stack(outs, 1)
    want = jnp.stack(outs2[5:], 1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-3, atol=2e-3)


def test_pallas_backend_inside_model():
    """Route the reduced model's attention+norm through the Pallas kernels
    (interpret mode) and compare against the XLA path."""
    from repro.kernels import ops

    cfg = get_config("mixtral-8x7b").reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    batch = _batch(cfg, b=1, s=16)
    ref = M.forward(cfg, params, batch)
    old = ops.BACKEND
    try:
        ops.BACKEND = "pallas_interpret"
        got = M.forward(cfg, params, batch)
    finally:
        ops.BACKEND = old
    np.testing.assert_allclose(
        np.asarray(got, np.float32), np.asarray(ref, np.float32), rtol=5e-3, atol=5e-3
    )


def test_moe_capacity_drops_are_bounded():
    """The expert layer is dropless: with every expert held, every token
    gets its k experts' output, however the router spreads them."""
    from repro.models.layers import moe_ffn, init_moe_ffn

    cfg = get_config("mixtral-8x7b").reduced()
    p = init_moe_ffn(jax.random.PRNGKey(3), cfg, jnp.float32)
    x = jax.random.normal(jax.random.PRNGKey(4), (64, cfg.d_model), jnp.float32)
    y, counts = moe_ffn(x, p, cfg)
    assert y.shape == x.shape
    assert bool((jnp.abs(y).sum(-1) > 0).all())
    assert int(counts[0]) == 64 * cfg.top_k and int(counts[1]) == cfg.n_experts
