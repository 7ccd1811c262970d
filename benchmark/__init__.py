"""The yardstick: one command (``python3 benchmark/run.py``) measures one cell
of ``BENCHMARK.json`` on the TPU it is started on.

Everything that belongs to one configuration, one cell or one per-layer
metric is a data file found by name (``configs/``, ``workloads/``,
``layer_metrics/``); code is per *kind* (``programs/`` builders,
``references/`` plain float32 models, ``jobs/`` measured loops,
``reducers/`` trace / span / counter readers). ``PERF.md`` says what each
metric means and how to add a cell with data files alone.
"""
