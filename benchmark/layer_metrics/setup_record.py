"""A sum over the program's own start-up record (docs/telemetry.md,
"Start-up record"): the rows ``deepspeed_tpu.utils.annotate
.setup_record()`` returns, each ``{name, start_s, end_s, parent,
attrs}`` on ``time.perf_counter``, the clock of ``run.t_open``. Set-up
is over before the profiler starts, so nothing here reads the trace.

Only rows that ENDED before the window opened count. Parameters:
``spans`` (row names), and what to add up over those rows: ``sum``
(attribute names; ``seconds`` is the row's own length) and/or ``count``
({attribute: [values]}: one for a row whose attribute is among them).
No such row, a program that keeps no record, or a run that opened no
window: None.
"""
import json


def rows_before_window(run):
    """The record's rows that ended before ``run.t_open``, read once;
    None where the program has no record or the run opened no window."""
    rows = getattr(run, "setup_rows", False)
    if rows is not False:
        return rows
    try:
        from deepspeed_tpu.utils.annotate import setup_record
    except ImportError:
        setup_record = None
    if setup_record is None or getattr(run, "t_open", None) is None:
        rows = None        # no record, or no window it could precede
    else:
        rows = [row for row in setup_record()
                if row["end_s"] <= run.t_open]
        _log(run, rows)
    run.setup_rows = rows
    return rows


def _log(run, rows):
    """The record as one line, and the benchmark's own set-up spans on
    the same clock (seconds before the window opened), so that a run's
    output says where its ``setup_s`` went."""
    def brief(row):
        attrs = dict(row["attrs"])
        names = attrs.pop("names", None)
        if names:
            top = sorted(names.items(), key=lambda kv: -kv[1][1])[:3]
            attrs["top"] = {n: [c, round(s, 3)] for n, (c, s) in top}
        return [row["name"], round(row["start_s"] - run.t_open, 3),
                round(row["end_s"] - row["start_s"], 3),
                {k: round(v, 3) if isinstance(v, float) else v
                 for k, v in attrs.items()}]

    run.log("start-up record [name, starts at (s, window opens at 0), "
            "seconds, attributes]: " + json.dumps([brief(r) for r in rows]))
    runs = []       # consecutive spans of one name, as one entry
    for name, start, end in run.spans.spans:
        if end > run.t_open:
            continue
        if runs and runs[-1][0] == name:
            runs[-1][2] += end - start
            runs[-1][3] += 1
        else:
            runs.append([name, start - run.t_open, end - start, 1])
    run.log("benchmark spans before the window [name, starts at, "
            "seconds, spans]: " + json.dumps(
                [[name, round(at, 3), round(seconds, 3), count]
                 for name, at, seconds, count in runs]))


def read(run, params):
    rows = rows_before_window(run)
    if rows is None:
        return None
    rows = [row for row in rows if row["name"] in params["spans"]]
    if not rows:
        return None
    total = 0.0
    for row in rows:
        attrs = dict(row["attrs"], seconds=row["end_s"] - row["start_s"])
        total += sum(attrs.get(field) or 0 for field in
                     params.get("sum", ()))
        total += sum(attrs.get(field) in values for field, values in
                     params.get("count", {}).items())
    return total
