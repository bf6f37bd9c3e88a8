"""SLO-aware admission control: the plane that ACTS on overload.

PR 6 built the measurement (open-loop loadgen, priority lanes, the
per-request ledger) and PR 10 built the detection (multi-window
ttft/tpot burn-rate watchdogs) — but nothing acted on either: past
saturation the queue grows without bound, every lane's TTFT blows up
together, and goodput collapses.  This module closes the control loop.
Three mechanisms, applied in order, all of them *admission-time* — an
admitted request is NEVER cancelled mid-stream by this plane:

* **Per-tenant token quotas** (``QuotaLedger``): a token-rate budget per
  tenant (the priority-lane label is the existing tenant axis), charged
  at submit with the request's worst-case token footprint
  (prompt + max_tokens).  Classic leaky bucket with an injectable
  clock: a tenant may burst to ``rate * burst_s`` tokens, then refills
  at ``rate`` tokens/second; an over-budget tenant is throttled (429 +
  Retry-After = its own refill time) BEFORE any global shedding — one
  noisy tenant can never force a global shed.  Configured with
  ``serve.py --quota tenant:toks_per_s[:burst_s]`` (repeatable) or
  ``ISTPU_QUOTAS="0:500,10:2000"``; tenants without a quota are
  unlimited.
* **Shed-on-burn**: while a page-severity ``ttft_burn``/``tpot_burn``
  watchdog (health.py) is firing, new submissions on the LOWEST
  priority lane(s) are shed with 429 + ``Retry-After`` — computed from
  the burn magnitude and the live queue-drain rate (the flight
  recorder's ``serve.completed`` delta), clamped to
  [``RETRY_AFTER_MIN_S``, ``RETRY_AFTER_MAX_S``].  Escalation is
  magnitude-driven: every ``ESCALATE_BURN_STEP`` of burn sheds one more
  lane from the bottom, but the HIGHEST (protected) lane is never shed
  when more than one lane exists.  With a single lane there is nothing
  to protect *relative to*: the lane duty-cycles (shed while burning,
  admit once the fast window clears), which is what turns the
  goodput-vs-rate curve's collapse into a plateau.
* **Degraded-mode chunked-prefill throttling**: while burning, the
  scheduler caps prefill chunk tokens per step
  (``prefill_token_budget``), so decode keeps its TPOT for the
  protected lane while prefill work queues instead of starving it.
  Work already queued is never held back by lane: the pending queue is
  priority-sorted (protected lanes admit first anyway), and freezing
  shed-lane backlog would only age it into guaranteed violations that
  re-ignite the burn when released.
* **Pressure shed**: queue depth far past the batch with the KV pool
  nearly exhausted sheds non-protected lanes even before a burn fires
  (the burn windows need finishing traffic to evaluate; a pool that
  can admit nothing produces none).

``ISTPU_ADMISSION=0`` is the kill switch: every decision is ``admit``,
no quota charges, no throttling — the A/B lever the
``bench_serve.py --rates`` plateau proof flips.

Everything lands as metrics (``istpu_admission_decisions_total
{action,lane}``, ``istpu_admission_shed_total{reason,lane}``,
``istpu_quota_tokens{tenant}``, ``istpu_admission_mode``) and as the
``GET /debug/admission`` payload; ``/healthz`` carries a compact
``admission`` block (field-level asserts only — the payload grows).
"""

from __future__ import annotations

import math
import os
import threading
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

# Retry-After bounds: never tell a client to hammer back sub-second,
# never park it longer than the slow burn window could possibly need
RETRY_AFTER_MIN_S = 1.0
RETRY_AFTER_MAX_S = 30.0
# every this much burn magnitude sheds one more lane from the bottom
ESCALATE_BURN_STEP = 4.0
# a lane unseen this long stops counting toward the shed ladder
LANE_TTL_S = 120.0
# pressure shed: pool nearly dry AND queue this deep past the batch
PRESSURE_FREE_FRAC = 0.03
PRESSURE_QUEUE_MIN = 8
# queue-delay shed: estimated queue wait (depth / live drain rate) past
# this multiple of the TTFT SLO sheds non-protected lanes.  This is the
# PREDICTIVE half of the loop: the burn watchdogs only see a violation
# when a late request finally COMPLETES, so a hard burst would queue an
# SLO's worth of doomed work before the reactive signal exists at all.
# 2x means any request admitted at the threshold was going to violate
# anyway — the shed never refuses work that could have met its SLO.
QUEUE_DELAY_SLO_FACTOR = 2.0


def _env_float(name: str, default: float) -> float:
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


def parse_quotas(spec) -> Dict[str, Tuple[float, float]]:
    """``tenant:toks_per_s[:burst_s]`` entries (comma string, list of
    such strings, or a dict) -> ``{tenant: (rate, burst_s)}``.  The
    tenant key is the lane label (stringified priority)."""
    if not spec:
        return {}
    if isinstance(spec, dict):
        out = {}
        for k, v in spec.items():
            rate, burst = (v if isinstance(v, (tuple, list)) else (v, None))
            out[str(k)] = (float(rate),
                           float(burst) if burst else DEFAULT_BURST_S)
        return out
    parts: List[str] = []
    if isinstance(spec, str):
        parts = spec.split(",")
    else:
        for item in spec:
            parts.extend(str(item).split(","))
    out = {}
    for part in parts:
        part = part.strip()
        if not part:
            continue
        fields = part.split(":")
        if len(fields) not in (2, 3):
            raise ValueError(
                f"quota spec {part!r} is not tenant:toks_per_s[:burst_s]"
            )
        tenant = fields[0].strip()
        rate = float(fields[1])
        if rate <= 0:
            raise ValueError(f"quota rate for {tenant!r} must be > 0")
        burst = float(fields[2]) if len(fields) == 3 else DEFAULT_BURST_S
        if burst <= 0:
            raise ValueError(f"quota burst for {tenant!r} must be > 0")
        out[tenant] = (rate, burst)
    return out


DEFAULT_BURST_S = 2.0  # a full bucket holds this many seconds of rate


class QuotaLedger:
    """Per-tenant token-rate budgets (leaky bucket, injectable clock).

    Debt model: a charge is allowed while the bucket is positive and
    takes the FULL token cost (the bucket may go negative), so the
    long-run admitted rate equals the configured rate regardless of
    request size; the burst cap only bounds the positive side.  A
    tenant with no configured quota is unlimited."""

    def __init__(self, quotas: Optional[Dict[str, Tuple[float, float]]]
                 = None, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self._lock = threading.Lock()
        self._cfg: Dict[str, Tuple[float, float]] = dict(quotas or {})
        # tenant -> [available_tokens, last_refill_t]
        self._state: Dict[str, List[float]] = {
            t: [rate * burst_s, None]
            for t, (rate, burst_s) in self._cfg.items()
        }
        self.throttled: Dict[str, int] = {t: 0 for t in self._cfg}

    @property
    def tenants(self) -> List[str]:
        return sorted(self._cfg)

    def _refill(self, tenant: str, now: float) -> List[float]:
        rate, burst_s = self._cfg[tenant]
        st = self._state[tenant]
        if st[1] is not None:
            st[0] = min(rate * burst_s, st[0] + (now - st[1]) * rate)
        st[1] = now
        return st

    def available(self, tenant: str,
                  now: Optional[float] = None) -> Optional[float]:
        """Post-refill bucket level; None for unlimited tenants."""
        if tenant not in self._cfg:
            return None
        now = self._clock() if now is None else now
        with self._lock:
            return self._refill(tenant, now)[0]

    def try_charge(self, tenant: str, tokens: int,
                   now: Optional[float] = None) -> bool:
        """Charge ``tokens`` against ``tenant``'s bucket.  True =
        admitted (bucket debited, possibly into debt); False = the
        tenant is over budget right now (nothing charged)."""
        if tenant not in self._cfg:
            return True
        now = self._clock() if now is None else now
        with self._lock:
            st = self._refill(tenant, now)
            if st[0] > 0:
                st[0] -= float(tokens)
                return True
            self.throttled[tenant] = self.throttled.get(tenant, 0) + 1
            return False

    def retry_after(self, tenant: str,
                    now: Optional[float] = None) -> float:
        """Seconds until the tenant's bucket is positive again (its own
        refill time), clamped to the global Retry-After bounds."""
        if tenant not in self._cfg:
            return RETRY_AFTER_MIN_S
        now = self._clock() if now is None else now
        rate, _ = self._cfg[tenant]
        with self._lock:
            avail = self._refill(tenant, now)[0]
        need = max(0.0, 1.0 - avail)  # back to one positive token
        return min(RETRY_AFTER_MAX_S, max(RETRY_AFTER_MIN_S, need / rate))

    def throttled_total(self) -> int:
        with self._lock:
            return sum(self.throttled.values())

    def snapshot(self, now: Optional[float] = None) -> Dict[str, Any]:
        now = self._clock() if now is None else now
        out: Dict[str, Any] = {}
        with self._lock:
            for tenant, (rate, burst_s) in sorted(self._cfg.items()):
                avail = self._refill(tenant, now)[0]
                burst = rate * burst_s
                out[tenant] = {
                    "rate_toks_per_s": rate,
                    "burst_tokens": round(burst, 1),
                    "available": round(avail, 1),
                    "used_frac": round(
                        min(1.0, max(0.0, 1.0 - avail / burst)), 4
                    ),
                    "throttled": self.throttled.get(tenant, 0),
                }
        return out


class AdmissionShed(Exception):
    """A submission the admission controller refused.  The serving
    layer maps it to HTTP 429 + ``Retry-After``; library callers catch
    it like any other submit-time rejection."""

    def __init__(self, reason: str, retry_after_s: float, message: str):
        super().__init__(message)
        self.reason = reason
        self.retry_after_s = retry_after_s


class Decision:
    """One admission verdict: ``action`` ∈ admit/shed/throttle,
    ``reason`` ∈ ok/burn/quota/pressure/queue, plus the Retry-After
    hint for the non-admit actions."""

    __slots__ = ("action", "reason", "retry_after_s")

    def __init__(self, action: str, reason: str = "ok",
                 retry_after_s: Optional[float] = None):
        self.action = action
        self.reason = reason
        self.retry_after_s = retry_after_s

    @property
    def admitted(self) -> bool:
        return self.action == "admit"


_MODE_CODE = {"off": 0.0, "normal": 1.0, "shed": 2.0}


class AdmissionController:
    """The decision point between detection and action.

    Consulted by ``Scheduler.submit`` (shed/throttle new work with 429 +
    Retry-After) and by the scheduler's step loop (cap prefill tokens
    per step while burning; queued work always drains — see
    ``Scheduler._prefill_burst``).  Reads live state only: the health
    sampler's firing watchdogs and flight-recorder ring, the
    scheduler's queue depths, and the engine's KV-pool pressure.

    Every collaborator is injectable (tests drive the decision table
    with stubs and a fake clock); all mutation happens under one lock —
    ``check_submit`` runs on the engine thread in the serving stack, but
    library callers may submit from anywhere."""

    BURN_SUFFIX = "_burn"

    def __init__(self, sched=None, engine=None, sampler=None,
                 quotas=None, metrics=None,
                 clock: Callable[[], float] = time.monotonic,
                 enabled: Optional[bool] = None,
                 prefill_cap_tokens: Optional[int] = None):
        self.enabled = (os.environ.get("ISTPU_ADMISSION", "1") != "0"
                        if enabled is None else enabled)
        self.sched = sched
        self.engine = engine
        self.sampler = sampler
        self._clock = clock
        self._lock = threading.Lock()
        spec = quotas if quotas is not None else os.environ.get(
            "ISTPU_QUOTAS")
        self.quota = QuotaLedger(parse_quotas(spec), clock=clock)
        # degraded-mode prefill throttle: cap on prefill chunk tokens
        # per scheduler step while burning (<=0 means "one advance")
        self.prefill_cap_tokens = (
            prefill_cap_tokens if prefill_cap_tokens is not None
            else int(_env_float("ISTPU_ADMISSION_PREFILL_TOKENS", 0)))
        # lanes recently offered traffic (lane label -> [last seen t,
        # ordering priority]): the shed ladder's rungs.  Integer lanes
        # (and numeric strings, normalized to int) order numerically by
        # their own value; NAMED tenant lanes ("acme") order by the
        # priority passed alongside (default 0) then lexicographically —
        # so string tenants keep working end to end while integer lanes
        # behave exactly as before.
        self._lanes: Dict[Any, List[float]] = {}
        # decision/shed tallies (python-side mirrors of the labeled
        # counters, for /debug/admission without a registry scrape)
        self._decisions: Dict[Tuple[str, str], int] = {}
        self._sheds: Dict[Tuple[str, str], int] = {}
        self._last_retry_after: Optional[float] = None
        self.metrics = metrics
        self._c_decisions = self._c_shed = self._g_quota = None
        if metrics is not None:
            self._c_decisions = metrics.counter(
                "istpu_admission_decisions_total",
                "Admission verdicts by action (admit/shed/throttle) and "
                "priority lane",
                labelnames=("action", "lane"),
            )
            self._c_shed = metrics.counter(
                "istpu_admission_shed_total",
                "Submissions refused with 429 + Retry-After, by reason "
                "(burn/quota/pressure/queue) and lane",
                labelnames=("reason", "lane"),
            )
            self._g_quota = metrics.gauge(
                "istpu_quota_tokens",
                "Per-tenant quota bucket level (tokens available; may "
                "go negative while a large charge drains)",
                labelnames=("tenant",),
            )
            metrics.gauge(
                "istpu_admission_mode",
                "Admission controller mode: 0 disabled, 1 normal, "
                "2 shedding (page-severity burn active)",
                fn=lambda: _MODE_CODE.get(self.mode(), 0.0),
            )

    # -- live inputs --------------------------------------------------------

    def _burn_value(self, rule: Optional[str] = None) -> float:
        """The strongest page-severity ``*_burn`` watchdog currently
        firing (0.0 = none); ``rule`` narrows the read to one rule.
        The sampler owns fire/clear hysteresis; this is a pure read."""
        if self.sampler is None or not getattr(self.sampler, "enabled",
                                               False):
            return 0.0
        worst = 0.0
        for f in self.sampler.firing():
            name = str(f.get("rule", ""))
            if rule is not None and name != rule:
                continue
            if (name.endswith(self.BURN_SUFFIX)
                    and f.get("severity") == "page"):
                try:
                    worst = max(worst, float(f.get("value") or 0.0))
                except (TypeError, ValueError):
                    worst = max(worst, 1.0)
        return worst

    def _queue_depth(self) -> int:
        s = self.sched
        if s is None:
            return 0
        return len(s.pending) + len(s.active) + len(s._prefilling)

    def _free_frac(self) -> float:
        eng = self.engine
        if eng is None:
            return 1.0
        try:
            n = eng.pc.n_blocks
            return eng.free_pages / n if n else 1.0
        except Exception:  # noqa: BLE001 — a stub without a pool
            return 1.0

    def _drain_rps(self) -> float:
        """Live completion rate (req/s) from the flight recorder's
        ``serve.completed`` counter over the fast burn window.  On a
        plane younger than the window the ring's ``delta`` degrades to
        "completions since boot", so the divisor must be the span the
        series actually covers — dividing by the nominal window would
        understate drain ~window/age-fold right after boot and make the
        predictive queue shed refuse a healthy warm-up burst."""
        sampler = self.sampler
        ring = getattr(sampler, "ring", None) if sampler is not None \
            else None
        if ring is None:
            return 0.0
        from .health import burn_windows

        fast = burn_windows()[0]
        d = ring.delta("serve.completed", fast)
        if not d:
            return 0.0
        window = fast
        began = getattr(ring, "began", lambda _n: None)("serve.completed")
        latest = ring.latest("serve.completed") \
            if hasattr(ring, "latest") else None
        if began is not None and latest is not None:
            step = float(getattr(ring, "step_s", 1.0) or 1.0)
            window = max(step, min(fast, latest[0] - began))
        return d / window

    # -- the shed ladder ----------------------------------------------------

    @staticmethod
    def _norm_lane(lane):
        """One lane identity for ``0``, ``"0"`` and friends: numeric
        labels normalize to int (numeric ordering, the pre-tenant
        behavior); anything else stays a string tenant label."""
        if isinstance(lane, str) and lane.lstrip("-").isdigit():
            return int(lane)
        return lane

    @staticmethod
    def _lane_sort_key(lane, prio: float):
        # int lanes order by value among themselves; string tenants by
        # (their priority, label) — ints first within equal priority so
        # mixed fleets shed legacy numeric lanes deterministically
        if isinstance(lane, int):
            return (float(lane), 0, "")
        return (float(prio), 1, str(lane))

    def note_lane(self, lane, now: Optional[float] = None,
                  priority: Optional[int] = None) -> None:
        lane = self._norm_lane(lane)
        if priority is None:
            priority = lane if isinstance(lane, int) else 0
        now = self._clock() if now is None else now
        with self._lock:
            self._lanes[lane] = [now, float(priority)]
            if len(self._lanes) > 64:  # bound: hostile lane churn
                for ln, (t, _p) in list(self._lanes.items()):
                    if now - t > LANE_TTL_S:
                        del self._lanes[ln]

    def _known_lanes(self, now: float) -> List:
        with self._lock:
            live = [(ln, p) for ln, (t, p) in self._lanes.items()
                    if now - t <= LANE_TTL_S]
        live.sort(key=lambda lp: self._lane_sort_key(*lp))
        return [ln for ln, _p in live]

    def shed_lanes(self, burn_value: Optional[float] = None,
                   now: Optional[float] = None) -> List:
        """The lanes currently being shed, lowest first.  Empty while
        not burning.  One lane per ``ESCALATE_BURN_STEP`` of burn
        magnitude; the highest lane is protected whenever more than one
        lane exists."""
        now = self._clock() if now is None else now
        burn = self._burn_value() if burn_value is None else burn_value
        if burn <= 0:
            return []
        lanes = self._known_lanes(now)
        if not lanes:
            return []
        if len(lanes) == 1:
            return lanes  # nothing to protect relative to: duty-cycle
        extra = int(max(0.0, burn) // ESCALATE_BURN_STEP)
        cutoff = min(1 + extra, len(lanes) - 1)
        return lanes[:cutoff]

    def _retry_after(self, burn_value: float) -> float:
        """Retry-After for a burn/pressure shed: the queue's drain-time
        estimate scaled by the burn magnitude, clamped.  A dead drain
        (nothing completing) answers the max — honest about a wedged
        server."""
        depth = self._queue_depth()
        drain = self._drain_rps()
        if drain <= 0:
            return RETRY_AFTER_MAX_S
        est = (depth + 1) / drain * max(1.0, min(burn_value, 8.0) / 2.0)
        return min(RETRY_AFTER_MAX_S, max(RETRY_AFTER_MIN_S, est))

    # -- the decision point -------------------------------------------------

    def check_submit(self, lane, tokens: int,
                     now: Optional[float] = None,
                     priority: Optional[int] = None) -> Decision:
        """The submit-time verdict for one request: ``tokens`` is its
        worst-case footprint (prompt + max_new_tokens); ``lane`` is the
        lane/tenant label (int or string — the tenant key for quotas
        either way), ``priority`` the ordering hint for string lanes.
        Order matters: the kill switch, then the tenant's own quota (a
        noisy tenant throttles before ANY global shed), then
        burn-driven lane shedding, then pool-pressure shedding."""
        now = self._clock() if now is None else now
        lane = self._norm_lane(lane)
        self.note_lane(lane, now, priority=priority)
        if not self.enabled:
            return self._record(lane, Decision("admit"))
        tenant = str(lane)
        avail = self.quota.available(tenant, now)
        if avail is not None and avail <= 0:
            # try_charge on a drained bucket charges nothing and counts
            # the throttle — the tenant verdict comes before any global
            # shed, with ITS OWN refill time as the Retry-After
            self.quota.try_charge(tenant, tokens, now)
            return self._record(lane, Decision(
                "throttle", "quota", self.quota.retry_after(tenant, now)))
        burn = self._burn_value()
        if burn > 0 and lane in self.shed_lanes(burn, now):
            # shed BEFORE charging: refused work must not drain the
            # tenant's future budget
            return self._record(lane, Decision(
                "shed", "burn", self._retry_after(burn)))
        if self._not_protected(lane, now):
            est = self._queue_delay_est()
            slo = getattr(self.sched, "slo_ttft_s", None) \
                if self.sched is not None else None
            if (slo and est is not None
                    and est > QUEUE_DELAY_SLO_FACTOR * slo):
                # predictive shed: this request would wait ~est seconds
                # before prefill even starts — past 2x the TTFT SLO it
                # is doomed on arrival, and admitting it only deepens
                # everyone's queue (the burst case the completion-based
                # burn signal is structurally too slow for)
                return self._record(lane, Decision(
                    "shed", "queue",
                    min(RETRY_AFTER_MAX_S,
                        max(RETRY_AFTER_MIN_S, est))))
            if (self._free_frac() < PRESSURE_FREE_FRAC
                    and self._queue_depth() >= PRESSURE_QUEUE_MIN):
                return self._record(lane, Decision(
                    "shed", "pressure", self._retry_after(1.0)))
        self.quota.try_charge(tenant, tokens, now)  # admitted: charge
        return self._record(lane, Decision("admit"))

    def _not_protected(self, lane, now: float) -> bool:
        """True when ``lane`` is fair game for queue/pressure sheds:
        everything except the highest known lane (which, with a single
        lane, is also fair game — there is nothing to protect
        relative to)."""
        lanes = self._known_lanes(now)
        return len(lanes) <= 1 or lane != lanes[-1]

    def _queue_delay_est(self) -> Optional[float]:
        """Estimated seconds a newly queued request waits before
        service: queue depth over the live drain rate.  None when there
        is no drain signal yet (cold start must not shed)."""
        drain = self._drain_rps()
        if drain <= 0:
            return None
        return self._queue_depth() / drain

    def _record(self, lane, d: Decision) -> Decision:
        ln = str(lane)
        with self._lock:
            key = (d.action, ln)
            self._decisions[key] = self._decisions.get(key, 0) + 1
            if not d.admitted:
                skey = (d.reason, ln)
                self._sheds[skey] = self._sheds.get(skey, 0) + 1
                self._last_retry_after = d.retry_after_s
        if self._c_decisions is not None:
            self._c_decisions.labels(d.action, ln).inc()
        if not d.admitted and self._c_shed is not None:
            self._c_shed.labels(d.reason, ln).inc()
        if self._g_quota is not None and str(lane) in self.quota.tenants:
            avail = self.quota.available(str(lane))
            if avail is not None:
                self._g_quota.labels(str(lane)).set(round(avail, 1))
        return d

    # -- scheduler-side hook (degraded mode) --------------------------------
    #
    # Deliberately NOT here: a per-lane hold that would freeze queued
    # shed-lane work out of prefill.  The pending queue is already
    # priority-sorted (protected lanes admit first), and freezing
    # backlog only ages it into guaranteed SLO violations that re-fire
    # the burn the moment it clears — a fire/clear oscillation.  Queued
    # work always drains; this plane refuses NEW work (check_submit)
    # and paces prefill (below).

    def prefill_token_budget(self) -> Optional[int]:
        """Prefill chunk tokens the scheduler may spend THIS step, or
        None for no throttle.  Active only while ``tpot_burn`` fires —
        the throttle exists to protect DECODE cadence (prefill queues
        so in-flight tokens keep flowing).  A ``ttft_burn`` does NOT
        arm it: there, prefill IS the path to first token, and pacing
        it would worsen exactly the SLO that is burning (shedding is
        that burn's actuator)."""
        if not self.enabled or self._burn_value("tpot_burn") <= 0:
            return None
        if self.prefill_cap_tokens > 0:
            return self.prefill_cap_tokens
        eng = self.engine
        chunk = getattr(eng, "prefill_chunk", None) if eng is not None \
            else None
        return int(chunk) if chunk else 1  # 1 token = one advance

    # -- export -------------------------------------------------------------

    def mode(self) -> str:
        if not self.enabled:
            return "off"
        return "shed" if self._burn_value() > 0 else "normal"

    def mode_code(self) -> float:
        return _MODE_CODE.get(self.mode(), 0.0)

    def shed_total(self) -> int:
        with self._lock:
            return sum(n for (reason, _ln), n in self._sheds.items()
                       if reason != "quota")

    def throttled_total(self) -> int:
        return self.quota.throttled_total()

    def health_block(self) -> Dict[str, Any]:
        """The compact ``admission`` block ``/healthz`` carries.  The
        payload GROWS over time — assert fields, never the exact body."""
        burn = self._burn_value()
        return {
            "mode": "shed" if burn > 0 else (
                "normal" if self.enabled else "off"),
            "shed_lanes": [str(ln) for ln in self.shed_lanes(burn)]
            if burn > 0 else [],
            "shed_total": self.shed_total(),
            "quota_throttled": self.throttled_total(),
        }

    def snapshot(self) -> Dict[str, Any]:
        """The ``GET /debug/admission`` payload."""
        if not self.enabled:
            return {"enabled": False, "mode": "off"}
        now = self._clock()
        burn = self._burn_value()
        with self._lock:
            decisions: Dict[str, Dict[str, int]] = {}
            for (action, lane), n in self._decisions.items():
                decisions.setdefault(action, {})[lane] = n
            sheds: Dict[str, Dict[str, int]] = {}
            for (reason, lane), n in self._sheds.items():
                sheds.setdefault(reason, {})[lane] = n
            last_retry = self._last_retry_after
        budget = self.prefill_token_budget()
        return {
            "enabled": True,
            "mode": "shed" if burn > 0 else "normal",
            "burn": {"value": round(burn, 3),
                     "shed_lanes": [str(ln)
                                    for ln in self.shed_lanes(burn, now)]},
            "lanes_seen": [str(ln) for ln in self._known_lanes(now)],
            "decisions": decisions,
            "shed_by_reason": sheds,
            "shed_total": self.shed_total(),
            "retry_after_last_s": (round(last_retry, 3)
                                   if last_retry is not None else None),
            "prefill_throttle": {"active": budget is not None,
                                 "budget_tokens": budget},
            "quota": {
                "tenants": self.quota.snapshot(now),
                "throttled_total": self.throttled_total(),
            },
            "queue": {
                "depth": self._queue_depth(),
                "drain_rps": round(self._drain_rps(), 3),
                "free_page_frac": round(self._free_frac(), 4),
            },
        }


def retry_after_header(retry_after_s: Optional[float]) -> Optional[str]:
    """HTTP ``Retry-After`` is integer seconds: ceil, floor at 1."""
    if retry_after_s is None:
        return None
    return str(max(1, int(math.ceil(retry_after_s))))
