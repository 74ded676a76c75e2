"""Published peaks of the chips the benchmark may run on (peaks.json),
keyed by the exact ``device_kind`` JAX reports. A kind that is not in
the table is an error, never a default; there is no CPU row."""
import json
import os

_PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                     "peaks.json")


def peaks_for(device_kind):
    with open(_PATH) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError("no published peaks for device kind {!r} (known: "
                       "{})".format(device_kind, ", ".join(sorted(table))))
    return table[device_kind]
