"""The chip benchmark of the loop-nest compiler's generated code and of the
serving engine.

``python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>``
runs one cell of ``BENCHMARK.json`` once; ``bench/harness.py`` says how, and
``bench/serving.py`` for a serving cell.
"""
