"""The benchmark of bluefog_tpu: ``BENCHMARK.json`` names the cells, the
command is ``python3 benchmark/run.py``, ``benchmark/spec.py`` says which name
finds which file, ``PERF.md`` says what is measured and why."""
