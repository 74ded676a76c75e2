#!/usr/bin/env python3
"""Fleet doctor CLI: merge a multi-host telemetry run directory (live
or post-mortem, crash bundles included), print the straggler/ICI-health
report, and emit a merged multi-process Perfetto trace.

    python bin/ds_fleet.py RUN_DIR                     # report to stdout
    python bin/ds_fleet.py RUN_DIR --json report.json  # fleet_report artifact
    python bin/ds_fleet.py RUN_DIR --trace merged.json # merged Chrome trace
    python bin/ds_fleet.py RUN_DIR --factor 2 --k 5    # detector thresholds
    python bin/ds_fleet.py RUN_DIR --strict            # exit 2 on flags
                                                       #   or divergence

``RUN_DIR`` is a ``telemetry.output_path`` whose per-job subdirectories
each hold one host's ``host_manifest.json`` + ``telemetry.jsonl`` (the
collector writes both; see docs/fleet.md). The merged trace gives each
host its own process lane, offset-corrected onto the reference host's
clock from step-completion skew.

Stdlib-only: the fleet modules mount under a synthetic package name
(the ``bin/ds_lint.py`` trick) so doctoring a crashed run never needs
jax installed.
"""
import argparse
import json
import os
import sys

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_fleet_modules():
    """Load telemetry.fleet.{aggregate,straggler} WITHOUT the
    deepspeed_tpu package __init__ chain (which imports jax): the fleet
    modules are stdlib-only by contract (fleet/__init__.py)."""
    import importlib
    import types
    name = "_ds_fleet_vendor"
    if name not in sys.modules:
        pkg = types.ModuleType(name)
        pkg.__path__ = [os.path.join(_REPO, "deepspeed_tpu",
                                     "telemetry", "fleet")]
        sys.modules[name] = pkg
    return (importlib.import_module(name + ".aggregate"),
            importlib.import_module(name + ".straggler"))


def _fmt_s(val):
    return "-" if val is None else "{:.4f}".format(val)


def print_report(report):
    print("fleet report: {} host(s), {} merged step(s)  [{}]".format(
        report["n_hosts"], len(report["records"]), report["run_dir"]))
    print()
    print("{:<24} {:>6} {:>8} {:>9} {:>8} {:>16}  {}".format(
        "host", "steps", "offset_s", "crashed", "manifest", "roles",
        "gaps"))
    offsets = report["offsets"]
    for host in report["hosts"]:
        # serving-role attribution (ISSUE 17): per-role serving_step
        # counts, so a disaggregated fleet's prefill/decode split is
        # visible in the host table
        roles = host.get("serving_roles") or {}
        role_str = ",".join("{}:{}".format(r, n)
                            for r, n in sorted(roles.items())) or "-"
        print("{:<24} {:>6} {:>8} {:>9} {:>8} {:>16}  {}".format(
            host["name"], host["steps"],
            "{:+.3f}".format(offsets.get(host["name"], 0.0)),
            "yes" if host["crashed"] else "no",
            "yes" if host["manifest"] else "MISSING",
            role_str,
            "; ".join(host["gaps"]) or "-"))
    if report["records"]:
        last = report["records"][-1]
        st = last.get("step_time")
        if st:
            print()
            print("last step {}: wall median {} min {} max {} "
                  "(slowest: {})".format(
                      last["step"], _fmt_s(st["median"]),
                      _fmt_s(st["min"]), _fmt_s(st["max"]),
                      st["max_host"]))
    straggler = report["straggler"]
    print()
    if straggler["flags"]:
        print("STRAGGLERS (>{}x fleet median for >= {} consecutive "
              "steps):".format(straggler["factor"], straggler["k"]))
        for flag in straggler["flags"]:
            print("  - host {host} [{metric}] {worst_ratio:.2f}x worst, "
                  "{steps} step(s), steps {first_step}..{last_step}"
                  .format(**flag))
    else:
        print("no stragglers flagged (factor {}, k {})".format(
            straggler["factor"], straggler["k"]))
    if report["ici_health"]:
        print("ici_health (achieved/nominal, last measured):")
        for host, classes in sorted(report["ici_health"].items()):
            print("  {:<24} {}".format(host, " ".join(
                "{}={:.3f}".format(cls, val)
                for cls, val in sorted(classes.items()))))
    else:
        print("ici_health: no measured exposed-wait walls in this run "
              "(micro/fused paths hide collectives inside one program; "
              "see docs/fleet.md)")
    divergence = report.get("divergence") or {}
    print()
    if divergence.get("mismatch"):
        print("PROGRAM DIVERGENCE: host(s) {} lowered a DIFFERENT "
              "collective sequence than reference host {} — the mesh "
              "hangs at the first divergent collective "
              "(docs/concurrency.md)".format(
                  ", ".join(divergence["divergent_hosts"]),
                  divergence["reference"]))
        for host, digest in sorted(divergence["digests"].items()):
            marker = " <-- DIVERGENT" \
                if host in divergence["divergent_hosts"] else ""
            print("  {:<24} fingerprint {}{}".format(host, digest,
                                                     marker))
    elif divergence.get("published"):
        print("program fingerprints: {} host(s) published, all agree "
              "({})".format(
                  divergence["published"],
                  next(iter(divergence["digests"].values()))))
    else:
        print("program fingerprints: none published (hosts ran without "
              "an audit/fingerprint pass; see docs/concurrency.md)")
    rescale = report.get("rescale") or {}
    print()
    if rescale.get("events"):
        print("RESCALE EVENTS ({} total, {} completed topology "
              "change(s); docs/elasticity.md):".format(
                  rescale.get("count", 0), rescale.get("completed", 0)))
        for ev in rescale["events"]:
            arrow = "-"
            if ev.get("old_world") is not None or \
                    ev.get("new_world") is not None:
                arrow = "{} -> {}".format(ev.get("old_world", "?"),
                                          ev.get("new_world", "?"))
            extras = []
            if ev.get("attempt") is not None:
                extras.append("attempt {}".format(ev["attempt"]))
            if ev.get("outcome"):
                extras.append(ev["outcome"])
            print("  - [{}] {:<18} world {:<10} {}{}".format(
                ev.get("host", "?"), ev.get("event", "?"), arrow,
                ev.get("reason", ""),
                " ({})".format(", ".join(extras)) if extras else ""))
    else:
        print("no rescale events (the run never changed topology)")
    router = report.get("router") or {}
    print()
    if router.get("events"):
        decisions = router.get("decisions") or {}
        print("ROUTER DECISIONS ({} event(s): {}; docs/fleet.md):".format(
            router.get("count", 0),
            ", ".join("{} {}".format(n, d)
                      for d, n in sorted(decisions.items()))))
        for ev in router["events"]:
            extras = []
            if ev.get("request_uid") is not None:
                extras.append("req {}".format(ev["request_uid"]))
            if ev.get("predicted_cost_s") is not None:
                extras.append("cost {:.4f}s".format(
                    ev["predicted_cost_s"]))
            detail = ev.get("detail") or {}
            if detail.get("to"):
                extras.append("-> {}".format(detail["to"]))
            print("  - [{}] {:<16} {}{}".format(
                ev.get("host") or "-", ev.get("decision", "?"),
                ev.get("reason", ""),
                " ({})".format(", ".join(extras)) if extras else ""))
    else:
        print("no router decisions (the run served without a fleet "
              "front-end)")


def main(argv=None):
    parser = argparse.ArgumentParser(
        description="fleet doctor: merge per-host telemetry, attribute "
                    "stragglers/ICI health, emit a merged trace")
    parser.add_argument("run_dir", help="telemetry output_path holding "
                        "per-host job directories")
    parser.add_argument("--json", dest="json_out", default=None,
                        help="write the fleet_report JSON artifact")
    parser.add_argument("--trace", dest="trace_out", default=None,
                        help="write a merged multi-process Chrome trace")
    parser.add_argument("--factor", type=float, default=None,
                        help="straggler deviation factor (default 1.5)")
    parser.add_argument("--k", type=int, default=None,
                        help="consecutive deviating steps to flag "
                             "(default 3)")
    parser.add_argument("--min-hosts", type=int, default=None,
                        help="minimum hosts for median attribution "
                             "(default 2)")
    parser.add_argument("--strict", action="store_true",
                        help="exit 2 when any straggler/ICI flag fired "
                             "or the host program fingerprints diverge")
    args = parser.parse_args(argv)
    aggregate, _straggler = _load_fleet_modules()
    if not os.path.isdir(args.run_dir):
        print("ds_fleet: {!r} is not a directory".format(args.run_dir),
              file=sys.stderr)
        return 1
    try:
        report = aggregate.merge_run(args.run_dir, factor=args.factor,
                                     k=args.k, min_hosts=args.min_hosts,
                                     trace_out=args.trace_out)
    except FileNotFoundError as err:
        print("ds_fleet: {}".format(err), file=sys.stderr)
        return 1
    print_report(report)
    if args.json_out:
        with open(args.json_out, "w") as fh:
            json.dump(report, fh, indent=2, sort_keys=True)
        print("\nfleet report -> {}".format(args.json_out))
    if report.get("trace"):
        trace = report["trace"]
        print("merged trace -> {} ({} events from {} host(s); load at "
              "ui.perfetto.dev)".format(trace["path"], trace["events"],
                                        trace["hosts_merged"]))
    if args.strict and (report["straggler"]["flags"] or
                        (report.get("divergence") or {}).get("mismatch")):
        return 2
    return 0


if __name__ == "__main__":
    sys.exit(main())
