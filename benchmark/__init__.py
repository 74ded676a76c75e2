"""The repo's benchmark: one command, cells named in BENCHMARK.json.

Everything that belongs to one configuration, traffic mix, runner, model
family or per-layer metric is a file of its own, found by the name the
data gives (see manifest.py); nothing here lists them.
"""
