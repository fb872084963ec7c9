"""Circuit breakers and the graceful-degradation ladder (ISSUE 7).

PR 4-6 gave every accelerator stage a *resolved-once* fallback chain:
``pallas -> jax -> numpy`` scoring, ``jax -> numpy`` partitioning, the
fused whole-pipeline program over both.  Resolution happens at pipeline
construction, so a RUNTIME device fault (compile failure, VMEM/HBM OOM,
hung kernel) mid-request escapes straight to the caller.  This module
upgrades resolved-once to **resolved-with-health**:

- :class:`CircuitBreaker` — one per resolved backend rung
  (:func:`rung_key`), the classic closed / open / half-open machine.
  ``threshold`` consecutive failures open the breaker; after
  ``cooldown_s`` one probe request is let through (half-open) and its
  outcome closes or re-opens it.  While open, the service skips the
  rung WITHOUT paying its failure latency.

- :func:`degradation_ladder` — the ordered list of
  ``(rung_name, PipelineConfig)`` a failed request walks, each rung a
  cumulative downgrade of the one above:

  1. ``full``            : the request's own config (PR 6 fused path
                           when eligible).
  2. ``unfused``         : same backends, staged host-driven pipeline
                           (``fused="off"``) — skips the fused program
                           but keeps device partition + scoring.
  3. ``score_jax``       : pallas scoring -> the jit jax scorer.
  4. ``score_numpy``     : accelerator scoring -> the numpy reference.
  5. ``partition_numpy`` : device partition sweep -> the host
                           vectorized engine.
  6. ``depth_2``         : deep hierarchies (``HierarchySpec`` depth
                           > 2) truncate to the classic two-level
                           node scheme — fewer coarsen/refine passes,
                           no grouping-level QAP search.
  7. ``refine_0``        : every level's refine AND polish rounds -> 0
                           (skip the swap/QAP/polish scoring loops
                           entirely).

  Rungs that do not apply to a config (numpy-only configs, flat
  hierarchy, depth <= 2) are elided; the FIRST rung is always the
  unmodified config and the LAST rung of every non-trivial ladder runs
  entirely on host numpy.

  **Quality bound:** rungs 2-5 only move WHERE the same algorithm runs
  — the repo's backend-equivalence guarantees (bit-identity oracles in
  tests/benchmarks) make their permutations bit-identical to the
  healthy path, so the objective score is unchanged.  Only the depth
  rungs change the result: ``depth_2`` trades the deep hierarchy's
  speed for the well-characterised two-level quality, and
  ``refine_0`` forfeits the (monotone) refinement improvement, i.e.
  the degraded score is at worst the UNREFINED hierarchical score —
  within 5% of flat on the benchmark suite (the ``hier`` entry's
  ``wh_ratio`` guard).

:class:`MappingService` (:mod:`repro.serve.engine`) walks the ladder on
failure or deadline expiry and records the rung that served the
request in ``MappingResult.stats["degraded"]``.
"""

from __future__ import annotations

import dataclasses
import threading
import time

from repro import obs
from repro.core.metrics import _BACKEND_CHAIN, get_evaluator
from repro.core.orderings import resolve_partition_backend
from repro.mapping.pipeline import PipelineConfig, fused_engages


class ServiceOverloaded(RuntimeError):
    """Admission queue full: the request was shed, not executed."""


class DeadlineExceeded(TimeoutError):
    """A ladder rung exceeded the request deadline (internal signal —
    the service degrades instead of surfacing this to callers)."""


# -- circuit breaker ------------------------------------------------------

class CircuitBreaker:
    """Closed / open / half-open breaker with probe-based recovery.

    closed    : requests flow; ``threshold`` CONSECUTIVE failures trip
                the breaker.
    open      : requests are refused (the ladder skips this rung) until
                ``cooldown_s`` has elapsed.
    half_open : exactly ONE probe request is admitted; success closes
                the breaker, failure re-opens it for another cooldown.

    ``clock`` is injectable for deterministic tests (defaults to
    ``time.monotonic``).
    """

    def __init__(self, threshold: int = 3, cooldown_s: float = 30.0,
                 clock=time.monotonic):
        if threshold < 1:
            raise ValueError(f"threshold must be >= 1, got {threshold}")
        self.threshold = int(threshold)
        self.cooldown_s = float(cooldown_s)
        self._clock = clock
        self._lock = threading.Lock()
        self._state = "closed"
        self._failures = 0
        self._opened_at = 0.0
        self._probing = False
        self.opens = 0      # cumulative trips (observability)
        self.failures = 0   # cumulative recorded failures

    def _state_locked(self) -> str:
        if (self._state == "open"
                and self._clock() - self._opened_at >= self.cooldown_s):
            self._state = "half_open"
            self._probing = False
        return self._state

    @property
    def state(self) -> str:
        with self._lock:
            return self._state_locked()

    def allow(self) -> bool:
        """May a request use this rung right now?"""
        with self._lock:
            st = self._state_locked()
            if st == "closed":
                return True
            if st == "half_open" and not self._probing:
                self._probing = True  # one probe per cooldown window
                return True
            return False

    def record_success(self) -> None:
        with self._lock:
            self._state = "closed"
            self._failures = 0
            self._probing = False

    def record_failure(self) -> None:
        with self._lock:
            self.failures += 1
            st = self._state_locked()
            if st == "half_open":
                self._trip_locked()  # failed probe: straight back open
                return
            self._failures += 1
            if self._failures >= self.threshold:
                self._trip_locked()

    def _trip_locked(self) -> None:
        self._state = "open"
        self._opened_at = self._clock()
        self._failures = 0
        self._probing = False
        self.opens += 1
        obs.counter("serve.breaker_trips")

    def stats(self) -> dict:
        with self._lock:
            return {"state": self._state_locked(), "opens": self.opens,
                    "failures": self.failures}


class BreakerBoard:
    """One :class:`CircuitBreaker` per rung key, created on demand.

    Keys come from :func:`rung_key`, so health is tracked per RESOLVED
    backend combination and shared across every scenario/config that
    lands on the same backends.
    """

    def __init__(self, threshold: int = 3, cooldown_s: float = 30.0,
                 clock=time.monotonic):
        self.threshold = threshold
        self.cooldown_s = cooldown_s
        self._clock = clock
        self._lock = threading.Lock()
        self._breakers: dict[str, CircuitBreaker] = {}

    def get(self, key: str) -> CircuitBreaker:
        with self._lock:
            br = self._breakers.get(key)
            if br is None:
                br = self._breakers[key] = CircuitBreaker(
                    self.threshold, self.cooldown_s, self._clock)
            return br

    def states(self) -> dict:
        with self._lock:
            items = list(self._breakers.items())
        return {k: br.stats() for k, br in items}


# -- the degradation ladder ----------------------------------------------

def rung_key(config: PipelineConfig) -> str:
    """The breaker key: which RESOLVED backends serve this config."""
    score = get_evaluator(config.score_backend)[0]
    part = resolve_partition_backend(config.partition_backend)
    parts = ["fused" if fused_engages(config) else "staged",
             f"score={score}", f"partition={part}"]
    spec = config.hierarchy
    if not spec.is_flat:
        # depth + total refine budget: a depth-degraded or refine-
        # stripped config is a DIFFERENT rung with its own breaker
        parts.append(f"depth={spec.depth}")
        parts.append(f"refine={spec.refine_rounds_total}")
    return "/".join(parts)


def degradation_ladder(config: PipelineConfig) -> list:
    """Ordered ``(rung_name, PipelineConfig)`` downgrades for ``config``.

    The first entry is always ``("full", config)`` unchanged; each
    later rung is a cumulative ``dataclasses.replace`` of the previous
    one (see the module docstring for the rung semantics and quality
    bound).  Rungs that would not change the config are elided, so a
    host-only config gets a single-rung ladder.
    """
    rungs = [("full", config)]
    cur = config

    def push(name, **changes):
        nonlocal cur
        new = dataclasses.replace(cur, **changes)
        if new != cur:
            cur = new
            rungs.append((name, new))

    if fused_engages(config):
        push("unfused", fused="off")
    for backend in _BACKEND_CHAIN[config.score_backend][1:]:
        push(f"score_{backend}", score_backend=backend)
    if config.partition_backend != "numpy":
        push("partition_numpy", partition_backend="numpy")
    # hierarchy-depth degradation, built with the spec's own combinators
    spec = cur.hierarchy
    if spec.depth > 2:
        push("depth_2", hierarchy=spec.truncated(2))
    spec = cur.hierarchy
    if not spec.is_flat and (spec.refine_rounds_total > 0
                             or spec.polish_rounds_total > 0):
        push("refine_0", hierarchy=spec.with_refine(rounds=0, polish=0))
    return rungs
