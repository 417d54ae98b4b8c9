"""Batched serving with the continuous-batching engine.

Demonstrates the request-handle lifecycle: ``submit(prompt)`` returns a
:class:`RequestHandle` immediately; the engine decodes every occupied slot
with one batched step per ``step()`` call, streaming tokens into an
optional per-request callback, and ``drain()`` runs the queue dry.

Run:  PYTHONPATH=src python examples/serve_batch.py
"""
import numpy as np
import jax

from repro.configs import get_config
from repro.device import use_compile_cache
from repro.models import model as M
from repro.serve import ServeConfig, ServingEngine


def main() -> None:
    use_compile_cache()
    cfg = get_config("mixtral-8x7b").reduced()  # tiny MoE+SWA decoder on CPU
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    eng = ServingEngine(
        cfg, params,
        ServeConfig(batch_slots=4, max_len=128, max_new_tokens=16, temperature=0.8),
    )
    rng = np.random.default_rng(0)
    streamed: dict[int, int] = {}

    def on_token(h, tok):  # fires as each token is harvested
        streamed[h.rid] = streamed.get(h.rid, 0) + 1

    handles = []
    for _ in range(6):  # more requests than slots -> continuous admission
        prompt = rng.integers(0, cfg.vocab, size=rng.integers(3, 12))
        handles.append(eng.submit(prompt.astype(np.int32), on_token=on_token))

    # block for one specific request (drives the engine), then run the rest dry
    first = handles[0].result()
    print(f"request {handles[0].rid} finished first-class: {first[:8]}...")
    results = eng.drain()
    for h in sorted(handles, key=lambda h: h.rid):
        assert h.done and results[h.rid] == h.tokens == h.result()
        assert streamed[h.rid] == len(h.tokens)
        print(f"request {h.rid}: {len(h.tokens)} tokens -> {h.tokens[:8]}...")
    assert len(results) == 6
    print("OK")


if __name__ == "__main__":
    main()
