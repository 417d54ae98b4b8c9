"""chip_smoke.py: its phases run on the CPU at reduced size, and it refuses
to report a result anywhere but on a TPU."""
import importlib.util
import json
from pathlib import Path

import pytest
import jax

from repro.configs import get_config
from repro.core import Daisy, Schedule
from repro.serve import ServeConfig

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", ROOT / "chip_smoke.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_main_refuses_a_non_tpu_backend(smoke, capsys):
    assert jax.default_backend() != "tpu"
    assert smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok": true' not in out.out + out.err
    assert "needs 1 TPU" in out.err


def test_daisy_resolves_the_interpreter_off_a_tpu():
    assert Daisy().backend == "pallas_interpret" and Daisy().interpret
    assert Schedule().interpret is None and Schedule().interpret_kernels
    assert Daisy(backend="pallas").interpret is False


@pytest.mark.parametrize("oracle", [True, False], ids=["oracle", "xla"])
def test_compiler_phase_mini(smoke, oracle):
    rows = smoke.compiler_phase("mini", nproma=8, klev=5, oracle=oracle)
    assert len(rows) == 2 * len(smoke.POLYBENCH) + 2
    assert all(r["backend"] == "pallas_interpret" for r in rows)
    assert sum(r["nest_kernels"] for r in rows) > 0
    assert all(r["max_rel_err"] <= 1e-5 for r in rows)


def test_compiler_phase_catches_a_wrong_output(smoke, monkeypatch):
    real = smoke.run_program

    def two_percent_off(daisy, prog, inputs):
        out, compile_s, hlo = real(daisy, prog, inputs)
        if daisy.backend != "xla":
            out = {k: v * 1.02 for k, v in out.items()}
        return out, compile_s, hlo

    monkeypatch.setattr(smoke, "run_program", two_percent_off)
    with pytest.raises(AssertionError, match="exceeds"):
        smoke.compiler_phase("mini", nproma=8, klev=5, oracle=False)


def test_serving_phase_reduced(smoke):
    cfg = get_config(smoke.MODEL).reduced()
    row = smoke.serving_phase(cfg, ServeConfig(batch_slots=2, max_len=64, max_new_tokens=4),
                              n_requests=3, prompt_lens=(4, 40), seed=0)
    assert row["requests"] == 3 and row["tokens"] == 12
    assert row["first_token_logit_rel_err"] <= smoke.LOGIT_TOL
    json.dumps(row)


def test_sharded_serving_phase_reduced(smoke):
    # the --chips 4 serving path on whatever devices exist (one, here)
    cfg = get_config(smoke.MODEL).reduced()
    n = jax.device_count()
    row = smoke.sharded_serving_phase(
        cfg, ServeConfig(batch_slots=2, max_len=64, max_new_tokens=3),
        n_requests=2, prompt_lens=(4, 20), seed=1, n_devices=n)
    assert row["mesh"] == {"data": 1, "model": n}
    assert row["first_token_logit_rel_err_vs_one_device"] <= smoke.LOGIT_TOL


def test_serve_raises_on_a_failed_request(smoke, monkeypatch):
    from repro.fault import Fault, FaultPlan
    from repro.models import model as M

    cfg = get_config(smoke.MODEL).reduced()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    prompts = smoke.prompts_for(cfg.vocab, 2, 4, 8, seed=0)
    plan = FaultPlan([Fault("serve.prefill", "error", key=1)])
    real = smoke.ServingEngine
    monkeypatch.setattr(smoke, "ServingEngine",
                        lambda *a, **kw: real(*a, fault_plan=plan, **kw))
    with pytest.raises(AssertionError, match="failed"):
        smoke.serve(cfg, params, ServeConfig(batch_slots=2, max_len=32,
                                             max_new_tokens=2), prompts)


def test_compile_cache_placement(monkeypatch):
    from repro import device

    assert device.CACHE_DIR == ROOT / ".jax_cache"
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/set/by/the/caller")
        device.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == before  # JAX's own
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        device.use_compile_cache()
        assert jax.config.jax_compilation_cache_dir == str(ROOT / ".jax_cache")
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
