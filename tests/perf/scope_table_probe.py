"""One traced run of a benchmark cell with its scope table logged,
whatever metrics the cell lists:

    chiprun -- python tests/perf/scope_table_probe.py --workload <cell> \
        --seed <n> --seconds 51

is ``python -m benchmark.run --trace 1`` with one line more of work:
after the cell's per-layer metrics it calls
``benchmark.layer_metrics.scope_busy_share.table(run)``, which logs
``scopes {scope: [s, % of busy, heaviest operations]}`` with what is
``unscoped``, ``unmapped`` and ``mixed`` (docs/telemetry.md, "Device
scopes"). For the cells whose BENCHMARK.json entries hold no scope
metric yet (extract, reasoning, ide, rag: ROADMAP Reach B5), and before
a ``perf_opt`` PR names an operation by its shape.
"""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark import run as command  # noqa: E402
from benchmark.layer_metrics import scope_busy_share  # noqa: E402


def main(argv):
    metrics = command.layer_metrics

    def with_table(run, outcome):
        values = metrics(run, outcome)
        scope_busy_share.table(run)
        return values

    command.layer_metrics = with_table
    return command.main(argv + ["--trace", "1"])


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
