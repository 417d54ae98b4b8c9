"""The check that decides ``correct`` in the serving cell, on the CPU at the
architecture's ``reduced()`` widths: the plain reference agrees with the
model code's own forward pass, a sound run is correct, and the control (the
reference with int8 weight products, reading the tokens it puts first) and
each fault planted in the timed path come out not correct: a layer left
out, the cache position off by one, a window the engine does not apply, a
decode step that returns its state unchanged, half of the slots left out,
and served tokens altered where they are produced."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from serving_cell import SEED, run, small_cell

from bench import harness, serving


@pytest.fixture(autouse=True)
def cpu_run(monkeypatch, tmp_path):
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {"flops_per_s": 1e12,
                                                            "bytes_per_s": 1e11})
    monkeypatch.setattr(harness, "OUT_DIR", tmp_path)


@pytest.mark.parametrize("window", [None, 16])
def test_reference_agrees_with_the_model_forward(window):
    """At float32 on the CPU both sides compute the same equations in
    another order (a scan over layers, fused attention against per-block
    softmax): they agree to float32 round-off, a relative 1e-4 of the
    logits' range, far below the 1e-2 and more that a wrong norm, rotary
    angle, head grouping or window moves them."""
    from repro.models.model import forward

    cell = small_cell(window)
    cfg = serving.model_config(cell.config)
    params = serving.make_weights(cfg, 11)
    tokens = np.random.default_rng(3).integers(0, cfg.vocab, 48).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        ref = np.asarray(cell.reference(params, tokens, cell.config["model"]))
        got = np.asarray(forward(cfg, params, {"tokens": jnp.asarray(tokens)[None]})[0])
    scale = np.abs(ref).max()
    assert np.abs(got - ref).max() <= 1e-4 * scale
    if window is not None:  # the window binds: without it the logits move
        with jax.default_matmul_precision("highest"):
            full = np.asarray(cell.reference(params, tokens, cell.config["model"], window=None))
        assert np.abs(full - ref).max() > 1e-2 * scale


def test_sound_run_is_correct():
    r = run(small_cell())
    assert r["correct"], r["checks"]
    assert r["checks"]["logit_gap"]["value"] <= r["checks"]["logit_gap"]["limit"]


def test_int8_control_is_not_correct():
    """The control at the same positions of a sound run's requests: the token
    the int8 reference puts first, read in the float32 reference.  At this
    size the check reads every finished request, as the run on the chip
    reads its sample, so that some hundreds of tokens are compared."""
    import time

    cell = small_cell()
    cfg = serving.model_config(cell.config)
    params = serving.make_weights(cfg, SEED)
    eng = serving.make_engine(cfg, params, cell.config["serve"])
    serving.warm_up(eng, cell.traffic, cell.config["serve"])
    reqs = serving.schedule(cell.traffic, cfg.vocab, SEED, 1.0, False)
    t0 = time.perf_counter()
    serving.OpenLoop(eng, reqs, t0).run(t0 + 1.5)
    serving.release(eng, reqs)
    done = [r for r in reqs if r.finished]
    assert sum(len(r.tokens) for r in done) >= 200
    got = serving.readings(cell, params, done, cell.config["controls"])
    limit = cell.config["limits"]["logit_gap"]
    assert got["logit_gap"] <= limit < got["int8"], got


def skip_layer(eng, params):
    """Layer 1 left out: its attention output and feed-forward output zeroed."""
    p = jax.tree_util.tree_map(lambda x: x, eng.params)
    for sub, w in (("mixer", "wo"), ("ffn", "wd")):
        p["layers"][sub][w] = p["layers"][sub][w].at[1].set(0)
    eng.params = p


def wrap_decode(change):
    """A hook that passes every decode step through ``change(real, params,
    states, tokens)``."""
    def hook(eng, params):
        real = eng._dispatch_greedy
        eng._dispatch_greedy = lambda p, s, t: change(real, p, s, t)
    return hook


def off_by_one(real, p, s, t):
    """Each token written, and rotated, one position past its own."""
    tok, new = real(p, dict(s, len=s["len"] + 1), t)
    return tok, dict(new, len=new["len"] - 1)


def unchanged(real, p, s, t):
    tok, _ = real(p, s, t)
    return tok, s


def half_slots(real, p, s, t):
    """The upper half of the slots left out: they keep their token and state."""
    tok, new = real(p, s, t)
    h = t.shape[0] // 2
    return (tok.at[h:].set(t[h:]),
            jax.tree_util.tree_map(lambda a, b: a.at[h:].set(b[h:]), new, s))


def altered_every_third_step():
    calls = {"n": 0}

    def change(real, p, s, t):
        tok, new = real(p, s, t)
        calls["n"] += 1
        return (tok + (calls["n"] % 3 == 0)) % 512, new
    return change


@pytest.mark.parametrize("fault", ["skip_layer", "off_by_one", "window", "unchanged",
                                   "half_slots", "altered"])
def test_fault_is_not_correct(fault):
    hooks = {"skip_layer": skip_layer, "off_by_one": wrap_decode(off_by_one),
             "unchanged": wrap_decode(unchanged), "half_slots": wrap_decode(half_slots),
             "altered": wrap_decode(altered_every_third_step())}
    # "window": the configuration states a window of 16 that every request
    # outgrows; the engine's cached attention does not apply it
    cell = small_cell(16 if fault == "window" else None)
    r = run(cell, engine_hook=hooks.get(fault))
    assert not r["correct"], r["checks"]
    assert r["checks"]["logit_gap"]["value"] > r["checks"]["logit_gap"]["limit"]
