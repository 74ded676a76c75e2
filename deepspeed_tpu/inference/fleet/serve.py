"""DisaggServer: the pump that drives a disaggregated serving fleet.

One server owns N prefill roles, M decode roles and a FleetRouter, and
replays the monolithic scheduler's step discipline across them: each
``step()`` admits queued requests through the router's predicted-cost
gate, runs at most one whole-request prefill per prefill host, moves
finished KV over the serialized page-slice wire (every handoff round-
trips through ``serialize_slice``/``deserialize_slice`` — the real
bytes, not an object reference), places the decode through the
router's straggler-aware picker, then fires one scheduler step on
every decode host. Degraded hosts with live streams get their
youngest slot preempt-and-migrated instead of a warning.

Metrics land in ONE shared ServingMetrics (TTFT at first-token from
the prefill half, decode/goodput from the decode halves), so a
caller reads the same snapshot keys it reads from a monolith.
"""
import time
from collections import deque

from ...utils.monitor import ServingMetrics
from .handoff import DEFAULT_HANDOFF_BLOCK, deserialize_slice
from .roles import DecodeRole, PrefillRole
from .router import FleetRouter

_UNSET = object()


class _Ticket:
    __slots__ = ("uid", "prompt", "max_new_tokens", "eos_token_id",
                 "arrival_t", "req", "denied", "payload", "slice",
                 "first_token_t")

    def __init__(self, uid, prompt, max_new_tokens, eos_token_id,
                 arrival_t):
        self.uid = uid
        self.prompt = prompt
        self.max_new_tokens = max_new_tokens
        self.eos_token_id = eos_token_id
        self.arrival_t = arrival_t
        self.req = None            # live decode-side request
        self.denied = False
        self.payload = None        # serialized slice awaiting a host
        self.slice = None
        self.first_token_t = None


class DisaggServer:

    def __init__(self, prefill_engines, decode_engines, metrics=None,
                 sampling=None, quantize=False,
                 block_size=DEFAULT_HANDOFF_BLOCK, router=None,
                 ttft_slo_s=None, tpot_slo_s=None,
                 admit_budget_factor=1.0, event_dir=None,
                 fingerprints=None, watchdog=None):
        self.metrics = metrics if metrics is not None else ServingMetrics()
        self.quantize = bool(quantize)
        self.router = router if router is not None else FleetRouter(
            ttft_slo_s=ttft_slo_s, tpot_slo_s=tpot_slo_s,
            admit_budget_factor=admit_budget_factor,
            event_dir=event_dir, watchdog=watchdog)
        fingerprints = fingerprints or {}
        self.prefill_roles = {}
        for name, engine in prefill_engines.items():
            role = PrefillRole(engine, sampling=sampling,
                               quantize=quantize, block_size=block_size)
            if self.router.enroll(name, "prefill", role=role,
                                  fingerprint=fingerprints.get(name)):
                self.prefill_roles[name] = role
        self.decode_roles = {}
        for name, engine in decode_engines.items():
            role = DecodeRole(engine, metrics=self.metrics,
                              sampling=sampling)
            if self.router.enroll(name, "decode", role=role,
                                  fingerprint=fingerprints.get(name)):
                self.decode_roles[name] = role
        assert self.prefill_roles and self.decode_roles, \
            "a disaggregated fleet needs at least one enrolled " \
            "prefill host and one enrolled decode host"
        self.queue = deque()
        self.pending = deque()     # tickets with a payload, no host yet
        self.tickets = {}
        self._next_uid = 0
        self.steps = 0

    # ------------------------------------------------------------ intake

    def submit(self, prompt, max_new_tokens=None, eos_token_id=_UNSET,
               arrival_t=None):
        """Queue a request; returns its ticket uid."""
        prompt = [int(t) for t in prompt]
        assert len(prompt) >= 1, "empty prompt"
        ticket = _Ticket(
            self._next_uid, prompt, max_new_tokens,
            eos_token_id if eos_token_id is not _UNSET else _UNSET,
            arrival_t if arrival_t is not None else time.perf_counter())
        self._next_uid += 1
        self.tickets[ticket.uid] = ticket
        self.queue.append(ticket)
        return ticket.uid

    @property
    def has_work(self):
        if self.queue or self.pending:
            return True
        if any(role.has_work for role in self.decode_roles.values()):
            return True
        return any(t.req is not None and t.req.state != "done"
                   for t in self.tickets.values())

    @property
    def preemptions(self):
        return sum(r.sched.preemptions
                   for r in self.decode_roles.values())

    # ------------------------------------------------------------ phases

    def _bucket_for(self):
        return next(iter(self.prefill_roles.values())).engine.bucket_for

    def _admit_and_prefill(self):
        bucket_for = self._bucket_for()
        for role in self.prefill_roles.values():
            # the router's cost gate first: denied requests never cost
            # a prefill slot
            while self.queue:
                ticket = self.queue[0]
                if self.router.admit(ticket.uid, len(ticket.prompt),
                                     bucket_for,
                                     queue_depth=len(self.queue) - 1):
                    break
                self.queue.popleft()
                ticket.denied = True
            if not self.queue:
                return
            ticket = self.queue[0]
            out = role.prefill_request(ticket.prompt,
                                       metrics=self.metrics)
            if out is None:
                return                     # pool full: stay queued
            self.queue.popleft()
            payload, _token, dt, bucket = out
            self.router.observe_prefill(bucket, dt)
            ticket.first_token_t = time.perf_counter()
            ttft = ticket.first_token_t - ticket.arrival_t
            self.metrics.record_ttft(ttft)
            self.router.observe_ttft(ttft)
            ticket.payload = payload
            self.pending.append(ticket)

    def _place_handoffs(self):
        for _ in range(len(self.pending)):
            ticket = self.pending[0]
            if ticket.slice is None:
                # the wire round-trip happens exactly once per handoff
                ticket.slice = deserialize_slice(ticket.payload)
                ticket.payload = None
            host = self.router.pick_decode_host(uid=ticket.uid)
            if host is None:
                return                     # no capacity: retry next step
            kwargs = {}
            if ticket.max_new_tokens is not None:
                kwargs["max_new_tokens"] = ticket.max_new_tokens
            if ticket.eos_token_id is not _UNSET:
                kwargs["eos_token_id"] = ticket.eos_token_id
            req = self.decode_roles[host].accept(ticket.slice, **kwargs)
            if req is None:
                return
            req.arrival_t = ticket.arrival_t
            req.first_token_t = ticket.first_token_t
            ticket.req = req
            ticket.slice = None
            self.pending.popleft()

    def _migrate_degraded(self):
        """One preempt-and-migrate per degraded host per step (instead
        of a straggler warning): its youngest decode slot moves to a
        healthy host, stream intact."""
        for host in list(self.router.hosts.values()):
            if host.kind != "decode":
                continue
            if not (host.straggler or host.unhealthy):
                continue
            if host.role is not None and host.role.youngest() is not None:
                self.router.preempt_migrate(host.name,
                                            quantize=self.quantize)

    def step(self):
        """Admit -> prefill+handoff -> place -> migrate-degraded ->
        one decode step per host."""
        self._admit_and_prefill()
        self._place_handoffs()
        self._migrate_degraded()
        for role in self.decode_roles.values():
            if role.has_work:
                role.step()
        self.steps += 1

    def run(self):
        """Drive step() until every ticket resolved. Returns
        ``{ticket_uid: generated tokens}`` — denied tickets map to
        None (the router's event log says why)."""
        while self.has_work:
            self.step()
        out = {}
        for uid, ticket in self.tickets.items():
            if ticket.denied:
                out[uid] = None
            else:
                assert ticket.req is not None and \
                    ticket.req.state == "done", \
                    "ticket {} never completed".format(uid)
                out[uid] = list(ticket.req.generated)
        return out

    # --------------------------------------------------------- reporting

    def handoff_stats(self):
        return {
            "handoffs": sum(r.handoffs
                            for r in self.prefill_roles.values()),
            "payload_bytes": sum(r.handoff_bytes
                                 for r in self.prefill_roles.values()),
            "quantized": self.quantize,
            "migrations": self.router.migrations,
        }
