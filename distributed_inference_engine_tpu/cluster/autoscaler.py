"""SLO-driven fleet autoscaling and zero-token-loss rolling upgrades.

Closes the telemetry → fleet-size loop: rounds 8-14 built the sensors
(unified ``MetricsRegistry`` scrape), the actuators (graceful drain,
supervised respawn with artifact cold-start, half-open rejoin), and the
fleet harness — but a human still had to watch the dashboards and pick a
fleet size. This module is the missing controller, in three parts:

- ``AutoscalerPolicy`` — a PURE, tick-based decision function. All state
  (hysteresis debounce, cooldowns) is counted in ticks, never wall-clock,
  and the victim/jitter source is seeded, so two same-seed runs over the
  same observations produce byte-identical decision ledgers. jax-free and
  I/O-free: unit-testable without a fleet.
- ``FleetAutoscaler`` — the driver loop on the coordinator. Each tick it
  SCRAPES (the same ``metrics_text`` poll an external Prometheus would
  trigger — no new telemetry plane), reduces the worker-labelled families
  to an ``SLOSnapshot``, asks the policy, and acts: scale-up reuses the
  supervisor's restart-hook machinery (spawn → ``add_worker`` →
  ``deploy_model(register_shards=False)`` artifact cold-start →
  ``lb.enter_half_open`` cautious rejoin); scale-down is the r12 graceful
  drain (``drain_worker(remove=True)``: affinity invalidated, in-flight
  finishes, zero token loss). At max fleet and still in breach it engages
  fleet-level admission shedding (``coordinator.set_admission_shed``) —
  typed ``overloaded`` + retry-after instead of unbounded queueing.
- ``RollingUpgrade`` — drain → artifact swap → golden-probe validate →
  half-open rejoin, one worker at a time. The golden probe is a greedy
  generation compared token-for-token against a reference captured from
  the pre-upgrade fleet; a mismatch (or a probe transport error) rolls
  the worker back to the old artifact and aborts the rollout.

Latency SLOs are measured over a SCRAPE WINDOW, not all-time: the reader
keeps the previous tick's merged cumulative histogram buckets and diffs,
so a burst moves the percentile immediately instead of being diluted by
hours of healthy history. Guard rails: the policy holds (never scales)
while the supervisor has a respawn in flight or any managed worker's
breaker is open — replacing broken capacity is the supervisor's job, and
scaling into a breaker-open worker would hand traffic to a corpse.
"""

from __future__ import annotations

import asyncio
import logging
import random
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Mapping, Optional, Sequence, Tuple

from ..config import AutoscalerConfig
from ..engine.types import GenerationRequest
from ..obs import collectors as obs_collectors
from ..obs.slo import BurnObjective, BurnRateEngine, violations_from_buckets
from .load_balancer import BREAKER_OPEN, BREAKER_HALF_OPEN

logger = logging.getLogger(__name__)

# decision actions (the ledger alphabet)
ACTION_UP = "up"
ACTION_DOWN = "down"
ACTION_HOLD = "hold"
ACTION_SHED_ON = "shed_on"
ACTION_SHED_OFF = "shed_off"


def percentile_from_buckets(cum: Mapping[str, float], q: float) -> float:
    """Interpolated quantile from cumulative histogram buckets
    (``le`` label → cumulative count, the OpenMetrics shape).

    Negative or non-monotone counts (a worker departed between scrapes,
    taking its share of the merged window with it) are clamped to
    monotone non-decreasing first. Mass in the ``+Inf`` bucket reports
    the largest finite bound — conservative, and the breach signal we
    want when latency blows past the bucket range."""
    if not cum:
        return 0.0
    inf = float("inf")
    items = sorted((inf if le == "+Inf" else float(le), max(0.0, v))
                   for le, v in cum.items())
    mono: List[Tuple[float, float]] = []
    run = 0.0
    for bound, v in items:
        run = max(run, v)
        mono.append((bound, run))
    total = mono[-1][1]
    if total <= 0:
        return 0.0
    target = q * total
    lo = 0.0
    prev_cum = 0.0
    for bound, cv in mono:
        if cv >= target:
            if bound == inf:
                return lo
            frac = (target - prev_cum) / max(1e-12, cv - prev_cum)
            return lo + frac * (bound - lo)
        lo, prev_cum = bound, cv
    return lo


@dataclass(frozen=True)
class SLOSnapshot:
    """One tick's reduced observation — everything the policy may see."""

    ttft_p95_s: float = 0.0        # windowed, merged across managed workers
    itl_p95_s: float = 0.0         # windowed decode-chunk p95
    queue_depth: float = 0.0       # mean waiting requests PER worker
    fleet_size: int = 0            # live managed workers
    window_requests: int = 0       # TTFT observations inside the window
    breaker_open: int = 0          # managed workers with breaker OPEN
    half_open: int = 0             # managed workers mid-trial (half-open)
    respawning: int = 0            # supervisor respawns in flight
    # False when the scrape reached NO managed worker this tick — an
    # all-zero snapshot then means "no information", not "all clear"
    scrape_ok: bool = True
    # True while the multi-window burn-rate engine has a breach engaged
    # (always False when ``slo_burn_enabled`` is off — the policy just
    # ORs it into the breach condition)
    burn_breach: bool = False


@dataclass(frozen=True)
class Decision:
    action: str                    # up | down | hold | shed_on | shed_off
    reason: str
    fleet_from: int
    fleet_to: int
    attainment: float
    tick: int

    def ledger_entry(self) -> Dict[str, Any]:
        """Canonical form compared across same-seed runs: the action
        SEQUENCE, without tick indices — live runs may observe an extra
        hold tick from scheduler jitter, which must not break replay
        equality."""
        return {"action": self.action, "reason": self.reason,
                "fleet_from": self.fleet_from, "fleet_to": self.fleet_to}


class AutoscalerPolicy:
    """Pure seeded policy: ``evaluate(SLOSnapshot) -> Decision``.

    Pressure is the worst ratio of observed/target over the enforced SLO
    dimensions (a target of 0 disables that dimension); attainment is its
    inverse capped at 1.0. Hysteresis: a breach must persist
    ``breach_ticks`` before scaling up, the all-clear must persist
    ``clear_ticks`` (AND the queue must be nearly empty) before scaling
    down, and each direction has its own post-action cooldown — so the
    controller cannot flap on a noisy window."""

    def __init__(self, cfg: Optional[AutoscalerConfig] = None) -> None:
        self.cfg = cfg or AutoscalerConfig()
        self._rand = random.Random(self.cfg.seed)
        self._tick = 0
        self._breach_run = 0
        self._clear_run = 0
        self._cooldown_until = 0       # tick index; applies to both directions
        self._shedding = False
        self.guard_holds = 0
        self.last_attainment = 1.0
        self.last_pressure_dim = ""
        self.ledger: List[Dict[str, Any]] = []       # canonical (non-hold)
        self.decisions: List[Decision] = []          # full per-tick detail

    # -- observation reduction ---------------------------------------------

    def _pressure(self, s: SLOSnapshot) -> Tuple[float, str]:
        c = self.cfg
        parts: List[Tuple[float, str]] = []
        if c.ttft_p95_target_s > 0 and s.window_requests > 0:
            parts.append((s.ttft_p95_s / c.ttft_p95_target_s, "ttft_p95"))
        if c.itl_p95_target_s > 0 and s.window_requests > 0:
            parts.append((s.itl_p95_s / c.itl_p95_target_s, "itl_p95"))
        if c.queue_depth_target > 0:
            parts.append((s.queue_depth / c.queue_depth_target,
                          "queue_depth"))
        if not parts:
            return 0.0, ""
        worst, dim = max(parts)
        return worst, dim

    # -- decision ----------------------------------------------------------

    def evaluate(self, snap: SLOSnapshot) -> Decision:
        self._tick += 1
        c = self.cfg
        pressure, dim = self._pressure(snap)
        att = 1.0 if pressure <= 0 else min(1.0, 1.0 / pressure)
        self.last_attainment = att
        self.last_pressure_dim = dim

        # guard first: a respawn in flight or an OPEN breaker means the
        # fleet is mid-repair — scaling now would fight the supervisor or
        # hand traffic to a corpse. Debounce state is left untouched so a
        # real breach resumes where it left off once the repair settles.
        if snap.respawning or snap.breaker_open:
            self.guard_holds += 1
            reason = ("guard:respawning" if snap.respawning
                      else "guard:breaker_open")
            return self._emit(ACTION_HOLD, reason, snap, att)

        # a failed scrape yields zeros everywhere — that is absence of
        # evidence, not evidence of health. Hold without touching the
        # debounce state so a real trend resumes once telemetry returns.
        if not snap.scrape_ok:
            self.guard_holds += 1
            return self._emit(ACTION_HOLD, "guard:no_data", snap, att)

        breach = att < c.scale_up_attainment or snap.burn_breach
        clear = (att >= c.scale_down_attainment
                 and snap.queue_depth
                 <= c.scale_down_queue_frac * c.queue_depth_target)
        if breach:
            self._breach_run += 1
            self._clear_run = 0
        elif clear:
            self._clear_run += 1
            self._breach_run = 0
        else:
            self._breach_run = 0
            self._clear_run = 0

        # degradation recovery outranks everything: the moment we leave
        # breach while shedding, stop refusing admissions
        if self._shedding and not breach:
            self._shedding = False
            return self._emit(ACTION_SHED_OFF, "recovered", snap, att)

        if breach:
            if snap.fleet_size < c.max_workers:
                if snap.half_open:
                    # capacity just added is still mid-trial — let its
                    # probe resolve before deciding we need even more
                    return self._emit(ACTION_HOLD, "guard:half_open",
                                      snap, att)
                if (self._breach_run >= c.breach_ticks
                        and self._tick >= self._cooldown_until):
                    self._cooldown_until = self._tick + c.cooldown_up_ticks
                    self._breach_run = 0
                    return self._emit(ACTION_UP, dim, snap, att,
                                      to=snap.fleet_size + 1)
                return self._emit(ACTION_HOLD, "breach_debounce", snap, att)
            if not self._shedding and self._breach_run >= c.shed_ticks:
                self._shedding = True
                return self._emit(ACTION_SHED_ON, "max_fleet_breach",
                                  snap, att)
            return self._emit(ACTION_HOLD, "at_max_fleet", snap, att)

        if (clear and snap.fleet_size > c.min_workers
                and self._clear_run >= c.clear_ticks
                and self._tick >= self._cooldown_until):
            self._cooldown_until = self._tick + c.cooldown_down_ticks
            self._clear_run = 0
            return self._emit(ACTION_DOWN, "slo_met", snap, att,
                              to=snap.fleet_size - 1)
        return self._emit(ACTION_HOLD, "steady", snap, att)

    def _emit(self, action: str, reason: str, snap: SLOSnapshot,
              att: float, to: Optional[int] = None) -> Decision:
        d = Decision(action=action, reason=reason,
                     fleet_from=snap.fleet_size,
                     fleet_to=snap.fleet_size if to is None else to,
                     attainment=round(att, 4), tick=self._tick)
        self.decisions.append(d)
        if action != ACTION_HOLD:
            self.ledger.append(d.ledger_entry())
        return d

    def pick_victim(self, candidates: Sequence[str]) -> str:
        """Seeded scale-down victim pick over a SORTED candidate list, so
        the choice sequence replays identically under the same seed."""
        cands = sorted(candidates)
        if not cands:
            raise ValueError("no scale-down candidates")
        return cands[self._rand.randrange(len(cands))]

    @property
    def shedding(self) -> bool:
        return self._shedding

    @property
    def ticks(self) -> int:
        return self._tick


class FleetAutoscaler:
    """The driver loop: scrape → reduce → decide → act, on an interval.

    ``spawn_hook(worker_id, None) -> (host, port)`` brings a fresh worker
    process up (same contract as the supervisor's restart hook — pass the
    same hook to share one spawn path). Scale-ups load the model as a
    pure replica (``register_shards=False``); the autoscaler manages
    replica sets, not registry shards."""

    def __init__(self, coordinator, model: str,
                 spawn_hook: Optional[Callable] = None,
                 cfg: Optional[AutoscalerConfig] = None,
                 managed: Optional[Sequence[str]] = None,
                 worker_prefix: str = "as",
                 load_timeout_s: float = 600.0) -> None:
        self.coord = coordinator
        self.model = model
        self.cfg = cfg or AutoscalerConfig()
        self.policy = AutoscalerPolicy(self.cfg)
        self._spawn_hook = spawn_hook
        self._managed: List[str] = list(
            managed if managed is not None else coordinator.lb.workers)
        self._worker_prefix = worker_prefix
        self._load_timeout_s = load_timeout_s
        self._spawn_n = 0
        self._scale_ups = 0
        self._scale_downs = 0
        self._task: Optional[asyncio.Task] = None
        self._running = False
        self._hist_prev: Dict[str, Dict[str, float]] = {}
        self.last_snapshot = SLOSnapshot()
        # SLO burn-rate engine (obs/slo.py), behind the config flag: fed
        # the same scrape-window TTFT deltas the attainment signal uses
        self.burn_engine: Optional[BurnRateEngine] = None
        if self.cfg.slo_burn_enabled:
            self.burn_engine = BurnRateEngine(
                [BurnObjective("ttft", goal=self.cfg.slo_burn_goal)],
                fast_ticks=self.cfg.slo_burn_fast_ticks,
                slow_ticks=self.cfg.slo_burn_slow_ticks,
                threshold=self.cfg.slo_burn_threshold)
        coordinator.obs_registry.add_collector(self._obs_collect)

    # -- lifecycle ----------------------------------------------------------

    async def start(self) -> None:
        if self._running:
            return
        self._running = True
        self._task = asyncio.create_task(self._loop())

    async def stop(self) -> None:
        self._running = False
        task, self._task = self._task, None
        if task is not None:
            task.cancel()
            try:
                await task
            except asyncio.CancelledError:
                pass

    async def _loop(self) -> None:
        while self._running:
            try:
                await self.tick()
            except asyncio.CancelledError:
                raise
            # graftlint: ok[swallowed-transport-error] a failed tick (scrape timeout, spawn error) must not kill the controller — it logs, holds the fleet as-is, and retries next interval
            except Exception:
                logger.exception("autoscaler tick failed; holding")
            await asyncio.sleep(self.cfg.interval_s)

    # -- observe ------------------------------------------------------------

    def _merged_window(self, fam_name: str, managed: set,
                       scrape_ok: bool) -> Tuple[Dict[str, float], float]:
        """Merge a worker-labelled histogram family's cumulative buckets
        across managed workers, then diff against the previous GOOD tick —
        returning the WINDOW's bucket counts and observation count. A
        failed scrape leaves the previous-tick state untouched: the
        all-time cumulative counts must not masquerade as one window's
        worth of observations when telemetry comes back."""
        fam = self.coord.obs_registry.get(fam_name)
        merged: Dict[str, float] = {}
        if fam is not None:
            for labels, child in fam.items():
                wid = labels.get("worker_id", "")
                if wid and wid not in managed:
                    continue
                items, _sum_v, _count = child.samples()
                for le, cum in items:
                    merged[le] = merged.get(le, 0.0) + cum
        if not scrape_ok:
            return {}, 0.0
        prev = self._hist_prev.get(fam_name, {})
        self._hist_prev[fam_name] = merged
        window = {le: max(0.0, cum - prev.get(le, 0.0))
                  for le, cum in merged.items()}
        return window, window.get("+Inf", 0.0)

    def _gauge_sum(self, fam_name: str, managed: set) -> float:
        fam = self.coord.obs_registry.get(fam_name)
        total = 0.0
        if fam is not None:
            for labels, child in fam.items():
                wid = labels.get("worker_id", "")
                if wid and wid not in managed:
                    continue
                total += float(child.value)
        return total

    async def observe(self) -> SLOSnapshot:
        """One scrape → one ``SLOSnapshot``. Latency/queue signals come
        from the registry families (the same exposition Prometheus sees);
        breaker/respawn guard signals come from the control plane, which
        is authoritative for membership."""
        await self.coord.metrics_text(
            refresh_workers=True,
            timeout_s=max(1.0, self.cfg.interval_s * 4))
        live = [w for w in self._managed if w in self.coord.lb.workers]
        managed = set(live)
        scrape_ok = (not live or any(
            w in self.coord._worker_metrics for w in live))
        ttft_window, n_req = self._merged_window(
            "engine_ttft_seconds", managed, scrape_ok)
        itl_window, _ = self._merged_window(
            "engine_decode_chunk_seconds", managed, scrape_ok)
        queue = self._gauge_sum("engine_waiting", managed)
        breaker_open = half_open = 0
        for wid in live:
            st = self.coord.lb.workers.get(wid)
            if st is None:
                continue
            if st.breaker_state == BREAKER_OPEN:
                breaker_open += 1
            elif st.breaker_state == BREAKER_HALF_OPEN:
                half_open += 1
        burn_breach = False
        if self.burn_engine is not None and scrape_ok:
            # one engine tick per GOOD scrape: the window deltas feed the
            # fast+slow rings; failed scrapes contribute nothing (windows
            # must not age on absent evidence)
            bad = violations_from_buckets(
                ttft_window, n_req, self.cfg.ttft_p95_target_s)
            transitions = self.burn_engine.observe(
                {"ttft": (n_req, bad)})
            burn_breach = self.burn_engine.breached()
            for tr in transitions:
                self.coord.events.emit(
                    "slo.burn_on" if tr["event"] == "burn_on"
                    else "slo.burn_off", objective=tr["objective"])
        snap = SLOSnapshot(
            ttft_p95_s=percentile_from_buckets(ttft_window, 0.95),
            itl_p95_s=percentile_from_buckets(itl_window, 0.95),
            queue_depth=queue / max(1, len(live)),
            fleet_size=len(live),
            window_requests=int(n_req),
            breaker_open=breaker_open,
            half_open=half_open,
            respawning=self.coord.respawns_in_flight(),
            scrape_ok=scrape_ok,
            burn_breach=burn_breach,
        )
        self.last_snapshot = snap
        return snap

    # -- act ----------------------------------------------------------------

    async def tick(self) -> Decision:
        snap = await self.observe()
        decision = self.policy.evaluate(snap)
        await self._act(decision)
        return decision

    async def _act(self, d: Decision) -> None:
        if d.action == ACTION_UP:
            await self._scale_up()
        elif d.action == ACTION_DOWN:
            await self._scale_down()
        elif d.action == ACTION_SHED_ON:
            self.coord.set_admission_shed(
                True, reason="fleet_overloaded",
                retry_after_s=self.cfg.shed_retry_after_s)
            logger.warning("autoscaler: fleet at max and SLO-violating — "
                           "admission shedding ON")
        elif d.action == ACTION_SHED_OFF:
            self.coord.set_admission_shed(False)
            logger.warning("autoscaler: pressure cleared — admission "
                           "shedding OFF")

    async def _scale_up(self) -> None:
        hook = self._spawn_hook or self.coord._restart_hook
        if hook is None:
            raise RuntimeError("autoscaler has no spawn hook (pass one, or "
                               "arm the supervisor restart hook)")
        wid = f"{self._worker_prefix}{self._spawn_n}"
        self._spawn_n += 1
        host, port = await hook(wid, None)
        self.coord.add_worker(wid, host, int(port))
        # a multi-model fleet scales up CATALOG-wide: the replacement must
        # be able to serve every model its peers hold, or affinity failover
        # routes a cold-model request to a worker that cannot take it. The
        # tracked model loads first so its requests land soonest.
        names = [self.model] + [n for n in self.coord._model_configs
                                if n != self.model]
        for name in names:
            mcfg = self.coord._model_configs[name]
            # artifact cold-start: the load RPC is the proof of life,
            # exactly as in the supervisor's respawn path
            await self.coord.deploy_model(mcfg, worker_ids=[wid],
                                          register_shards=False,
                                          load_timeout_s=self._load_timeout_s)
        self._managed.append(wid)
        # KV fabric pre-warm BEFORE half-open: the trial probe should hit
        # imported prefix pages, not pay a cold prefill (best-effort)
        for name in names:
            await self.coord.prewarm_worker(wid, model=name)
        # cautious rejoin: first pick is the trial probe
        self.coord.lb.enter_half_open(wid)
        self._scale_ups += 1
        logger.warning("autoscaler: scaled UP — %s at %s:%s (half-open), "
                       "fleet=%d", wid, host, port, len(self._managed))

    async def _scale_down(self) -> None:
        live = [w for w in self._managed if w in self.coord.lb.workers]
        victim = self.policy.pick_victim(live)
        # graceful drain: quarantine (spreading stops, affinity bindings
        # invalidated), in-flight finishes on the worker, then removal —
        # no stream loses a token
        await self.coord.drain_worker(victim, remove=True)
        if victim in self._managed:
            self._managed.remove(victim)
        self._scale_downs += 1
        logger.warning("autoscaler: scaled DOWN — drained %s, fleet=%d",
                       victim, len(self._managed))

    # -- introspection ------------------------------------------------------

    @property
    def managed_workers(self) -> List[str]:
        return list(self._managed)

    def get_stats(self) -> Dict[str, Any]:
        by_action: Dict[str, int] = {}
        for e in self.policy.ledger:
            by_action[e["action"]] = by_action.get(e["action"], 0) + 1
        return {
            "fleet_size": len([w for w in self._managed
                               if w in self.coord.lb.workers]),
            "slo_attainment": self.policy.last_attainment,
            "ticks": self.policy.ticks,
            "scale_ups": self._scale_ups,
            "scale_downs": self._scale_downs,
            "guard_holds": self.policy.guard_holds,
            "shedding": self.policy.shedding,
            "decisions_by_action": by_action,
            "ledger": list(self.policy.ledger),
            "last_snapshot": {
                "ttft_p95_s": self.last_snapshot.ttft_p95_s,
                "queue_depth": self.last_snapshot.queue_depth,
                "window_requests": self.last_snapshot.window_requests,
            },
            "burn": (self.burn_engine.get_stats()
                     if self.burn_engine is not None else None),
            "burn_ledger": (self.burn_engine.ledger()
                            if self.burn_engine is not None else []),
        }

    def _obs_collect(self) -> None:
        obs_collectors.apply_autoscaler(self.coord.obs_registry,
                                        self.get_stats())
        if self.burn_engine is not None:
            obs_collectors.apply_slo(self.coord.obs_registry,
                                     self.burn_engine.get_stats())


@dataclass
class _UpgradeStats:
    upgraded: int = 0
    probe_failures: int = 0
    rollbacks: int = 0
    in_progress: int = 0


class RollingUpgrade:
    """Zero-token-loss rolling upgrade over a replica set.

    Per worker: graceful drain (in-flight streams finish; new work fails
    over) → process swap via ``swap_hook(worker_id, info) -> (host,
    port)`` → load the NEW model config (the artifact swap) → golden
    probe: a greedy generation compared token-for-token against a
    reference captured from the pre-upgrade fleet → half-open rejoin.
    A probe mismatch or error rolls that worker back to the OLD config
    (spawned via ``rollback_hook``, defaulting to ``swap_hook``) and
    aborts the remaining rollout — a bad artifact never takes a second
    worker. Only after EVERY worker passes does the coordinator's stored
    model config flip to the new one (so supervisor respawns and
    autoscaler scale-ups load the new artifact)."""

    def __init__(self, coordinator, model: str, new_cfg,
                 swap_hook: Callable,
                 rollback_hook: Optional[Callable] = None,
                 probe_prompt: Optional[Sequence[int]] = None,
                 probe_new_tokens: int = 8,
                 load_timeout_s: float = 600.0,
                 drain_timeout_s: Optional[float] = None) -> None:
        self.coord = coordinator
        self.model = model
        self.new_cfg = new_cfg
        self.swap_hook = swap_hook
        self.rollback_hook = rollback_hook or swap_hook
        self.probe_prompt = list(probe_prompt or (7, 11, 13, 17))
        self.probe_new_tokens = probe_new_tokens
        self.load_timeout_s = load_timeout_s
        self.drain_timeout_s = drain_timeout_s
        self.stats = _UpgradeStats()
        self.events: List[Dict[str, Any]] = []
        coordinator.obs_registry.add_collector(self._obs_collect)

    async def _capture_reference(self) -> List[int]:
        res = await self.coord.submit(
            self.model, prompt=self.probe_prompt,
            max_new_tokens=self.probe_new_tokens, no_cache=True,
            request_id="upgrade-golden-ref")
        return list(res["tokens"])

    async def _load_and_probe(self, worker_id: str, cfg,
                              expected: List[int]) -> bool:
        """Artifact load + golden probe DIRECTLY against the worker (it is
        quarantined — no coordinator routing can reach it yet)."""
        client = self.coord.router.client_for(worker_id)
        try:
            await client.load_model(cfg, timeout=self.load_timeout_s)
            req = GenerationRequest(
                prompt=list(self.probe_prompt),
                max_new_tokens=self.probe_new_tokens, temperature=0.0,
                request_id=f"upgrade-probe-{worker_id}")
            results = await client.generate(self.model, [req],
                                            timeout=self.load_timeout_s)
            got = list(results[0].tokens)
        # graftlint: ok[swallowed-transport-error] a probe that cannot even reach the swapped worker IS a failed probe — the rollback path below owns the consequence
        except Exception:
            logger.exception("upgrade probe against %s errored", worker_id)
            return False
        if got != expected:
            logger.error("upgrade probe MISMATCH on %s: got %s, "
                         "expected %s", worker_id, got, expected)
            return False
        return True

    async def _swap(self, worker_id: str, info, hook: Callable) -> None:
        meta = dict(info.metadata)
        host, port = await hook(worker_id, info)
        self.coord.add_worker(worker_id, host, int(port), **meta)
        # no traffic until the probe passes
        self.coord.lb.quarantine(worker_id)

    async def _await_peer_capacity(self, wid: str,
                                   timeout_s: float = 10.0) -> None:
        """Hold the rollout until a worker other than ``wid`` takes
        traffic. The worker upgraded last rejoined HALF_OPEN and serves
        nothing until the LB's probe closes its trial; draining the next
        one inside that window leaves the model with no healthy replica,
        and live requests fail with ``NoHealthyWorkerError`` whenever the
        probe loop lags (a loaded host). Bounded: a fleet whose peers never
        recover rolls on as before."""
        if not any(w != wid for w in self.coord.lb.workers):
            return
        deadline = asyncio.get_running_loop().time() + timeout_s
        while asyncio.get_running_loop().time() < deadline:
            if any(s.worker_id != wid
                   for s in self.coord.lb.healthy_workers()):
                return
            await asyncio.sleep(0.01)

    async def run(self, worker_ids: Optional[Sequence[str]] = None
                  ) -> Dict[str, Any]:
        targets = list(worker_ids if worker_ids is not None
                       else self.coord.lb.workers)
        old_cfg = self.coord._model_configs[self.model]
        expected = await self._capture_reference()
        self.stats.in_progress = 1
        try:
            for wid in targets:
                info = self.coord.router.workers.get(wid)
                if info is None:
                    continue
                await self._await_peer_capacity(wid)
                await self.coord.drain_worker(
                    wid, timeout_s=self.drain_timeout_s, remove=True)
                await self._swap(wid, info, self.swap_hook)
                if await self._load_and_probe(wid, self.new_cfg, expected):
                    self.coord.router.mark_worker_success(wid)
                    self.coord.lb.enter_half_open(wid)
                    self.stats.upgraded += 1
                    self.events.append({"worker": wid, "event": "upgraded"})
                    continue
                # probe failed: roll THIS worker back to the old artifact
                # and abort the rollout — already-upgraded workers passed
                # their probes and stay
                self.stats.probe_failures += 1
                self.coord.remove_worker(wid)
                await self._swap(wid, info, self.rollback_hook)
                restored = await self._load_and_probe(wid, old_cfg, expected)
                if restored:
                    self.coord.router.mark_worker_success(wid)
                    self.coord.lb.enter_half_open(wid)
                else:
                    # rollback probe failed too — leave the worker out of
                    # both planes rather than serving wrong tokens
                    self.coord.remove_worker(wid)
                self.stats.rollbacks += 1
                self.events.append({"worker": wid, "event": "rolled_back",
                                    "restored": restored})
                # flight recorder: a rollback is a post-mortem-worthy
                # incident — bundle the fleet's state at the abort point
                self.coord.events.emit("upgrade.rollback", worker=wid,
                                       model=self.model, restored=restored)
                self.coord._fire_postmortem("upgrade_rollback",
                                            dead_workers=(wid,))
                return {"completed": False, "aborted_at": wid,
                        "upgraded": self.stats.upgraded,
                        "rolled_back": restored, "events": list(self.events)}
            # full success: future respawns/scale-ups load the new artifact
            self.coord._model_configs[self.model] = self.new_cfg
            return {"completed": True, "upgraded": self.stats.upgraded,
                    "events": list(self.events)}
        finally:
            self.stats.in_progress = 0

    def get_stats(self) -> Dict[str, Any]:
        return {
            "upgraded": self.stats.upgraded,
            "probe_failures": self.stats.probe_failures,
            "rollbacks": self.stats.rollbacks,
            "in_progress": self.stats.in_progress,
        }

    def _obs_collect(self) -> None:
        obs_collectors.apply_upgrade(self.coord.obs_registry,
                                     self.get_stats())
