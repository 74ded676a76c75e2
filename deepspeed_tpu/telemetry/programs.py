"""Compile observatory: a registry of every jitted program the engines
run, fed from the ``engine._jit_priced`` seam (training; zero/stream.py
rides the same seam) and the inference engine's prefill/decode trace
caches.

Per program it records the key, the XLA ``cost_analysis`` dict, the
call count, and the recompile count — read from the jit function's own
executable cache (``fn._cache_size()``) where the jax build exposes it,
so a silent shape-driven recompile under a stable engine key is still
counted. What a program's making COST (tracing, lowering, compile or
load, by program) is the start-up record's to say
(``engine.startup_report()``, docs/telemetry.md "Start-up record").

Two anomaly detectors flag into ``flags`` (and warn loudly, once each):

* **recompile storms** — a single program family compiling more than
  ``recompile_storm_threshold`` distinct executables (the classic cause:
  unbounded ``inference.prefill_buckets``, every new prompt length a new
  trace);
* **accidental full replication** — a program whose committed input
  sharding keeps a leaf larger than ``replicated_leaf_bytes`` fully
  replicated on a multi-device mesh (the classic cause: a missing
  partition rule silently multiplying HBM by the mesh size).

The registry is alive whenever telemetry is enabled (per program call:
a memoized key lookup, one counter update, and the cache-size probe);
``telemetry.programs`` tunes the thresholds.
"""
import time

from ..utils.logging import logger
# the rule implementations (and their default thresholds) live in the
# analysis package: the ahead-of-time auditor and this runtime registry
# share ONE implementation and one threshold config
# (``telemetry.programs``), so the two paths cannot drift. The names
# are re-exported here for back-compat (telemetry/config.py imports
# them from this module).
from ..analysis.rules import (RECOMPILE_STORM_THRESHOLD_DEFAULT,
                              REPLICATED_LEAF_BYTES_DEFAULT,
                              recompile_storm_finding,
                              replicated_leaf_finding)

_MAX_FLAGS = 64


def _key_str(key):
    if isinstance(key, str):
        return key
    if isinstance(key, tuple):
        return "/".join(_key_str(k) for k in key)
    return repr(key)


def _cache_size(fn):
    """The jit function's own executable-cache size — XLA's ground truth
    for how many programs this callable compiled. None when this jax
    build exposes no introspection."""
    try:
        size = fn._cache_size
    except AttributeError:
        return None
    try:
        return int(size() if callable(size) else size)
    except Exception:  # noqa: BLE001 - introspection only
        return None


class ProgramRegistry:
    """See module docstring. ``snapshot()`` is what crash bundles embed
    as their ``programs`` section."""

    def __init__(self, storm_threshold=RECOMPILE_STORM_THRESHOLD_DEFAULT,
                 replicated_leaf_bytes=REPLICATED_LEAF_BYTES_DEFAULT):
        self.storm_threshold = int(storm_threshold)
        self.replicated_leaf_bytes = int(replicated_leaf_bytes)
        self.programs = {}
        self.families = {}
        self.flags = []
        self._flagged = set()
        self._key_strs = {}         # hot-path memo: key -> key_str

    def _memo_key_str(self, key):
        try:
            cached = self._key_strs.get(key)
        except TypeError:           # unhashable key component
            return _key_str(key)
        if cached is None:
            cached = self._key_strs[key] = _key_str(key)
        return cached

    @staticmethod
    def _new_entry(family):
        """The ONE registry-entry shape (every intake path shares it).
        ``registered`` flips when the first CALL runs the family bump +
        sharding audit — price() may create the entry first, and must
        not swallow those side effects."""
        return {
            "family": family,
            "registered": False,
            "registered_wall": time.time(),
            "calls": 0,
            "executables": 1,
            "recompiles": 0,
            "flops": None,
            "cost_analysis": None,
        }

    # ----------------------------------------------------------- intake
    def observe_call(self, key, fn, args=None, family=None):
        """One invocation of the jitted program behind ``key``. First
        sight registers it (and audits the args' committed shardings);
        every call updates the call/recompile counters."""
        key_str = self._memo_key_str(key)
        entry = self.programs.get(key_str)
        if entry is None:
            entry = self.programs[key_str] = self._new_entry(
                family or key_str.split("/", 1)[0])
        if not entry["registered"]:
            entry["registered"] = True
            self._bump_family(entry["family"])
            if args is not None:
                self._audit_shardings(key_str, args)
        entry["calls"] += 1
        size = _cache_size(fn)
        if size is not None and size > entry["executables"]:
            entry["recompiles"] += size - entry["executables"]
            entry["executables"] = size
            finding = recompile_storm_finding(key_str, size,
                                              self.storm_threshold)
            if finding is not None:
                self._flag(finding.key, finding.message)
        return entry

    def observe_trace(self, family, key):
        """A NEW jitted trace in a keyed program family (the inference
        engine's prefill/decode caches): counts distinct keys per family
        and flags a storm when the family outgrows the threshold (e.g.
        unbounded prefill buckets)."""
        key_str = _key_str((family, key))
        if key_str in self.programs:
            return self.programs[key_str]
        entry = self.programs[key_str] = self._new_entry(family)
        entry["registered"] = True
        count = self._bump_family(family)
        finding = recompile_storm_finding(
            family, count, self.storm_threshold,
            hint="bound its key space (e.g. inference.prefill_buckets)")
        if finding is not None:
            self._flag(finding.key, finding.message)
        return entry

    def price(self, key, costs):
        """Attach the program's cost analysis (computed once by the
        telemetry flops cache) to its registry entry. May run before the
        first observe_call — it only fills pricing fields, never the
        registration side effects (family count, sharding audit)."""
        key_str = self._memo_key_str(key)
        entry = self.programs.get(key_str)
        if entry is None:
            entry = self.programs[key_str] = self._new_entry(
                key_str.split("/", 1)[0])
        costs = costs or {}
        entry["flops"] = float(costs.get("flops", 0.0) or 0.0)
        entry["cost_analysis"] = {str(k): float(v)
                                  for k, v in costs.items()
                                  if isinstance(v, (int, float))}

    # ---------------------------------------------------------- auditing
    def _bump_family(self, family):
        fam = self.families.setdefault(family, {"count": 0, "storm": False})
        fam["count"] += 1
        if fam["count"] > self.storm_threshold:
            fam["storm"] = True
        return fam["count"]

    def _audit_shardings(self, key_str, args):
        """Flag program inputs whose COMMITTED sharding fully replicates
        a large leaf across a multi-device mesh."""
        try:
            import jax
            if jax.device_count() <= 1:
                return
            for i, leaf in enumerate(jax.tree_util.tree_leaves(args)):
                nbytes = getattr(leaf, "nbytes", 0) or 0
                sharding = getattr(leaf, "sharding", None)
                if sharding is None or \
                        not getattr(sharding, "is_fully_replicated", False):
                    continue
                finding = replicated_leaf_finding(
                    key_str, "arg{}".format(i), nbytes,
                    jax.device_count(), self.replicated_leaf_bytes)
                if finding is not None:
                    # one flag per program is enough (the AOT auditor
                    # reports per-leaf; the runtime registry dedupes)
                    self._flag("replicated_leaf:" + key_str,
                               finding.message)
                    return
        except Exception:  # noqa: BLE001 - audit must never perturb a step
            pass

    def _flag(self, flag_key, message):
        if flag_key in self._flagged:
            return
        self._flagged.add(flag_key)
        if len(self.flags) < _MAX_FLAGS:
            self.flags.append({"key": flag_key, "message": message,
                               "wall": time.time()})
        logger.warning("compile observatory: %s", message)

    # ---------------------------------------------------------- snapshot
    def snapshot(self):
        return {
            "programs": {k: dict(v) for k, v in self.programs.items()},
            "families": {k: dict(v) for k, v in self.families.items()},
            "flags": list(self.flags),
            "storm_threshold": self.storm_threshold,
            "replicated_leaf_bytes": self.replicated_leaf_bytes,
        }
