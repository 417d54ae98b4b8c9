"""A small copy of the serving cell for CPU tests: the architecture's
``reduced()`` widths, four slots of 256 tokens, short prompts and answers
at a high rate, and a lead-in and traced round of half a second."""
import time

from bench import harness, serving
from repro.configs import get_config

CELL = "danube-3-4b.chat"
SEED = (1 << 33) + 5


def small_cell(window=None):
    config, traffic = CELL.split(".")
    spec = harness.load_json(harness.ROOT / "BENCHMARK.json")
    cell = harness.make_cell(spec, CELL, config, f"bench/configs/{config}.json", traffic)
    red = get_config(cell.config["arch"]).reduced()
    cell.config["model"] = {k: getattr(red, k) for k in cell.config["model"]}
    cell.config["model"]["window"] = window
    cell.config["serve"] = {"batch_slots": 4, "max_len": 256, "max_new_tokens": 64,
                            "temperature": 0.0}
    t = cell.traffic
    cell.traffic = dict(t, rate_per_s=20.0, lead_in_s=0.5, trace_s=0.5,
                        prompt_tokens=dict(t["prompt_tokens"], median=24, min=8, max=128),
                        output_tokens=dict(t["output_tokens"], median=8, min=4, max=32))
    return cell


def run(cell, seconds=1.0, trace=False, engine_hook=None, seed=SEED):
    return serving.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            engine_hook=engine_hook)
