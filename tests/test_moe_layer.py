"""The expert layer told which experts it holds (``layers.moe_ffn``), YaRN's
rotary frequencies and the ring write of a prefill, at small sizes on the
CPU in float32."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.configs.base import Yarn
from repro.models import layers as L

CFG = get_config("mellum2-12b-a2.5b").reduced()


def whole_layer(seed=3):
    """The reduced Mellum2 expert layer with every one of its experts held."""
    cfg = dataclasses.replace(CFG, experts_held=0)
    return cfg, L.init_moe_ffn(jax.random.PRNGKey(seed), cfg, jnp.float32)


def per_token(x, p, cfg):
    """The layer one token at a time: softmax over every expert's logit,
    the top k renormalized, each chosen expert's SwiGLU weighted by its gate."""
    x, p = np.asarray(x, np.float64), jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64), p)
    out = np.zeros_like(x)
    for t in range(x.shape[0]):
        logits = x[t] @ p["router"]
        probs = np.exp(logits - logits.max())
        probs /= probs.sum()
        top = np.argsort(-probs, kind="stable")[:cfg.top_k]
        for e in top:
            h = x[t] @ p["wg"][e]
            f = h / (1 + np.exp(-h)) * (x[t] @ p["wu"][e])
            out[t] += probs[e] / probs[top].sum() * (f @ p["wd"][e])
    return out


def test_reduced_config_holds_fewer_experts_than_it_routes_over():
    assert CFG.n_layers == len(CFG.layer_types) == 4
    assert CFG.layer_types == ("sliding", "sliding", "sliding", "full")
    assert CFG.top_k <= CFG.held_experts < CFG.n_experts
    assert [CFG.layer_window(l) for l in range(4)] == [16, 16, 16, None]
    assert [CFG.layer_yarn(l) is not None for l in range(4)] == [False, False, False, True]


@pytest.mark.parametrize("shares", [2, 4])
def test_shares_of_disjoint_held_sets_add_up_to_the_whole_layer(shares):
    """As the chips of an expert-parallel deployment hold experts 0-15,
    16-31, 32-47 and 48-63 of 64, here ``shares`` chips hold equal runs of
    the 8 experts: each computes its own experts' part, and the parts add up
    to the layer with every expert held, and to the per-token loop."""
    cfg, p = whole_layer()
    x = jax.random.normal(jax.random.PRNGKey(5), (48, cfg.d_model), jnp.float32)
    held = cfg.n_experts // shares
    total, pairs = 0.0, 0
    for k in range(shares):
        part = {"router": p["router"],
                **{w: p[w][k * held:(k + 1) * held] for w in ("wg", "wu", "wd")}}
        y, counts = L.moe_ffn(x, part, cfg, first=k * held)
        total = total + y
        pairs += int(counts[0])
        assert int(counts[1]) <= held
    whole, counts = L.moe_ffn(x, p, cfg)
    assert pairs == int(counts[0]) == 48 * cfg.top_k
    np.testing.assert_allclose(np.asarray(total), np.asarray(whole), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(whole), per_token(x, p, cfg), rtol=1e-4, atol=1e-4)


def test_dropless_when_every_token_picks_the_same_experts():
    """A router that sends every token to experts 1 and 2: each of them
    computes all 64 tokens (a capacity of 1.25 tokens' share would have
    dropped most), equal to the per-token loop."""
    cfg, p = whole_layer()
    router = np.full((cfg.d_model, cfg.n_experts), -1.0, np.float32)
    router[:, 1], router[:, 2] = 2.0, 1.0
    p = dict(p, router=jnp.asarray(router))
    x = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (64, cfg.d_model), jnp.float32))
    y, counts = L.moe_ffn(x, p, cfg)
    assert [int(c) for c in counts] == [64 * 2, 2]
    np.testing.assert_allclose(np.asarray(y), per_token(x, p, cfg), rtol=1e-4, atol=1e-4)
    # held on another chip, the two experts leave nothing here
    part = {"router": p["router"], **{w: p[w][4:] for w in ("wg", "wu", "wd")}}
    y, counts = L.moe_ffn(x, part, cfg, first=4)
    assert [int(c) for c in counts] == [0, 0] and not np.asarray(y).any()


def test_padding_routes_nowhere():
    cfg, p = whole_layer()
    x = jax.random.normal(jax.random.PRNGKey(7), (16, cfg.d_model), jnp.float32)
    valid = jnp.arange(16) < 10
    y, counts = L.moe_ffn(x, p, cfg, valid=valid)
    full, _ = L.moe_ffn(x[:10], p, cfg)
    assert int(counts[0]) == 10 * cfg.top_k
    np.testing.assert_allclose(np.asarray(y[:10]), np.asarray(full), rtol=1e-6, atol=1e-6)
    assert not np.asarray(y[10:]).any()


MELLUM_YARN = get_config("mellum2-12b-a2.5b").yarn


def test_yarn_frequencies_match_the_closed_form():
    """Mellum2's full layers (d 128, theta 5e5, factor 16 from 8192,
    beta 32 and 1): the correction range is floor(128 ln(8192 / (32 2 pi))
    / (2 ln 5e5)) = 18 to ceil(128 ln(8192 / (2 pi)) / (2 ln 5e5)) = 35;
    below it the plain frequencies, above it a sixteenth of them, a linear
    blend in between, and cos and sin scaled by 0.1 ln 16 + 1."""
    y = MELLUM_YARN
    assert y.attention_factor == pytest.approx(0.1 * math.log(16) + 1)
    inv, scale = L.yarn_frequencies(128, 5e5, y)
    plain = 1.0 / 5e5 ** (np.arange(0, 128, 2) / 128)
    low = math.floor(128 * math.log(8192 / (32 * 2 * math.pi)) / (2 * math.log(5e5)))
    high = math.ceil(128 * math.log(8192 / (2 * math.pi)) / (2 * math.log(5e5)))
    assert (low, high) == (18, 35)
    ramp = np.clip((np.arange(64) - low) / (high - low), 0, 1)
    want = plain * (1 - ramp) + plain / 16 * ramp
    np.testing.assert_allclose(inv, want, rtol=1e-6)
    np.testing.assert_allclose(inv[:19], plain[:19], rtol=1e-6)
    np.testing.assert_allclose(inv[35:], plain[35:] / 16, rtol=1e-6)
    assert scale == y.attention_factor


def test_rope_with_yarn_rotates_by_the_scaled_frequencies():
    y = Yarn(factor=4.0, original_max_position_embeddings=64, attention_factor=1.5)
    x = jax.random.normal(jax.random.PRNGKey(8), (1, 5, 2, 16), jnp.float32)
    pos = jnp.arange(5)[None] * 7
    got = np.asarray(L.rope(x, pos, 1e4, y))
    inv, scale = L.yarn_frequencies(16, 1e4, y)
    ang = np.asarray(pos)[0][:, None, None] * inv
    x1, x2 = np.asarray(x)[..., :8], np.asarray(x)[..., 8:]
    want = scale * np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                                   x2 * np.cos(ang) + x1 * np.sin(ang)], -1)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)
    # without YaRN the plain rotation, unscaled
    plain = np.asarray(L.rope(x, pos, 1e4))
    assert not np.allclose(plain, got)


@pytest.mark.parametrize("clen,n_new,s", [(0, 20, 24), (0, 5, 8), (3, 1, 1)])
def test_ring_write_keeps_the_latest_positions(clen, n_new, s):
    """Slot j of a ring of 8 holds the latest written position p with
    p % 8 == j; padding past ``n_new`` is never written."""
    w = 8
    ring = np.full((1, w, 1), -1.0, np.float32)
    ring[0, :clen, 0] = np.arange(clen)  # what an earlier call left
    new = (clen + np.arange(s, dtype=np.float32))[None, :, None]
    got = np.asarray(L.ring_write(jnp.asarray(ring), jnp.asarray(new), clen, n_new))[0, :, 0]
    want = ring[0, :, 0].copy()
    for p in range(clen, clen + n_new):
        want[p % w] = p
    np.testing.assert_array_equal(got, want)
