"""A small copy of the Mellum2 serving cell for CPU tests: the
architecture's ``reduced()`` widths (one period of three sliding layers and
one full layer, a window of 16, 4 of 8 experts held, top-2), four slots of
256 tokens, prompts of 24-128 tokens (every one outgrows the window) and
short answers at a high rate, a lead-in and traced round of half a second."""
import dataclasses
import time

from bench import harness, serving
from repro.configs import get_config

CELL = "mellum2-12b-a2.5b.code"
SEED = (1 << 33) + 7


def small_cell():
    cell = harness.load_cell(CELL)
    red = get_config(cell.config["arch"]).reduced()
    model = {k: getattr(red, k) for k in cell.config["model"]}
    model["layer_types"] = list(red.layer_types)
    model["yarn"] = dataclasses.asdict(red.yarn)
    cell.config["model"] = model
    cell.config["serve"] = {"batch_slots": 4, "max_len": 256, "max_new_tokens": 64,
                            "temperature": 0.0}
    t = cell.traffic
    cell.traffic = dict(t, rate_per_s=20.0, lead_in_s=0.5, trace_s=0.5,
                        prompt_tokens=dict(t["prompt_tokens"], median=40, min=24, max=128),
                        output_tokens=dict(t["output_tokens"], median=8, min=4, max=32))
    return cell


def run(cell, seconds=1.0, trace=False, engine_hook=None, seed=SEED):
    return serving.run_cell(cell, seed, seconds, trace, time.perf_counter(),
                            engine_hook=engine_hook)
