"""flowchaos deterministic fault injection.

The only way the repo exercised failure before r17 was hand-written
kill-one-worker legs; every OTHER seam where a network-wide answer is
assembled — sink writes, the member->coordinator submit/sync hops, the
Kafka adapters, the serve publisher fan-out — ran fault-free in every
test. This module injects faults at exactly those seams, DETERMINISTICALLY,
so a chaos leg is a reproducible test, not a flake generator:

- A **fault plan** names sites and per-call failure probabilities::

      sink.write:p=0.05;mesh.submit:p=0.02@seed=7

  parsed by :func:`parse_plan`; configured via the ``-faults=`` flag or
  the ``FLOWTPU_FAULTS`` env fallback (flagless processes — the same
  contract as ``FLOWTPU_TRACE``).

- **Latency injection** (flowguard, r20): a site may carry a
  ``delay=<seconds>`` parameter instead of pure failure::

      sink.write:delay=0.02;bus.poll:p=0.5:delay=0.1@seed=7

  A hit at a delay site SLEEPS (outside the plan lock) instead of
  raising — a slow sink / slow upstream, not a dead one, which is the
  overload shape ``make guard-parity`` soaks. ``delay=`` without ``p=``
  means p=1 (every call stalls). A site is either a failure site
  (delay 0) or a latency site (delay > 0); the Bernoulli stream
  discipline is identical for both.

- Each site draws from its OWN ``random.Random`` seeded by
  ``(seed, site)``, so the Bernoulli sequence at one site is a pure
  function of (plan, call index at that site) — thread interleaving
  ACROSS sites, or adding a new site to the plan, cannot change another
  site's outcomes. Same plan + same per-site call order => same faults.

- An injected fault raises :class:`FaultInjected`, a subclass of
  ``OSError`` — the same type family real transport failures surface
  as, so every retry/dead-letter/rejoin path treats injected and real
  faults identically (the whole point: the chaos soak drives the REAL
  recovery machinery, not a parallel test-only path).

- **Off mode is one attribute read**: call sites guard with
  ``if FAULTS.active and FAULTS.should_fail("site"): ...`` — with no
  plan configured, the seam costs a single attribute load.

Known sites (kept in :data:`KNOWN_SITES` so a typo'd plan fails loudly
instead of silently injecting nothing): ``sink.write``,
``mesh.submit``, ``mesh.sync``, ``kafka.send``, ``kafka.poll``,
``serve.publish``, ``bus.produce``, ``bus.poll``, ``gateway.poll``.
"""

from __future__ import annotations

# flowlint: lock-checked
# (fault rolls happen on every pipeline thread — worker, flusher,
# member drivers, publisher; one lock guards the per-site RNG streams)

import random
import threading
import time
from typing import Optional

from ..obs import REGISTRY

# The seams the dataplane actually threads FAULTS through. configure()
# rejects unknown sites: a chaos leg whose plan names a site nothing
# checks would "pass" by injecting nothing.
KNOWN_SITES = frozenset({
    "sink.write", "mesh.submit", "mesh.sync", "kafka.send", "kafka.poll",
    "serve.publish",
    # r18: the in-process bus (collector-side chaos — the produce path
    # a collector/mocker rides and the fetch path every consumer rides)
    # and the flowgate subscription poll
    "bus.produce", "bus.poll", "gateway.poll",
})


class FaultInjected(OSError):
    """An injected transport/IO fault. Subclasses OSError so the normal
    retry/recovery paths handle it exactly like a real failure."""


def parse_plan_full(spec: str) -> tuple[dict[str, tuple[float, float]], int]:
    """``"site:p=0.05;site2:delay=0.02@seed=7"`` ->
    ({site: (p, delay)}, seed). Raises ValueError on malformed specs,
    unknown sites, probabilities outside [0, 1], or delays outside
    [0, 60]. ``delay=`` without ``p=`` implies p=1 (every call stalls)."""
    spec = spec.strip()
    seed = 0
    if "@" in spec:
        spec, _, tail = spec.rpartition("@")
        key, _, val = tail.partition("=")
        if key.strip() != "seed":
            raise ValueError(f"expected @seed=N, got @{tail!r}")
        seed = int(val)
    sites: dict[str, tuple[float, float]] = {}
    for part in filter(None, (p.strip() for p in spec.split(";"))):
        site, sep, params = part.partition(":")
        site = site.strip()
        if not sep:
            raise ValueError(
                f"fault site {part!r} needs :p=<prob> and/or "
                f":delay=<seconds>")
        if site not in KNOWN_SITES:
            raise ValueError(
                f"unknown fault site {site!r} (known: "
                f"{', '.join(sorted(KNOWN_SITES))})")
        p: Optional[float] = None
        delay = 0.0
        for param in filter(None, (s.strip() for s in params.split(":"))):
            key, _, val = param.partition("=")
            key = key.strip()
            if key == "p":
                p = float(val)
                if not 0.0 <= p <= 1.0:
                    raise ValueError(
                        f"fault site {site!r}: p={p} outside [0, 1]")
            elif key == "delay":
                delay = float(val)
                if not 0.0 <= delay <= 60.0:
                    raise ValueError(
                        f"fault site {site!r}: delay={delay} outside "
                        f"[0, 60] seconds")
            else:
                raise ValueError(
                    f"fault site {site!r}: expected p=<prob> or "
                    f"delay=<seconds>, got {param!r}")
        if p is None:
            if delay <= 0.0:
                raise ValueError(
                    f"fault site {site!r}: expected p=<prob>, "
                    f"got {params!r}")
            p = 1.0  # delay-only site: every call stalls
        sites[site] = (p, delay)
    return sites, seed


def parse_plan(spec: str) -> tuple[dict[str, float], int]:
    """``"site:p=0.05;site2:p=0.02@seed=7"`` -> ({site: p}, seed) —
    the original probability-only view (delay parameters are parsed
    and validated, then dropped; :func:`parse_plan_full` keeps them)."""
    sites, seed = parse_plan_full(spec)
    return {name: pd[0] for name, pd in sites.items()}, seed


class _Site:
    __slots__ = ("p", "delay", "rng", "rolls", "injected", "delayed")

    def __init__(self, p: float, seed: int, name: str, delay: float = 0.0):
        self.p = p
        self.delay = delay  # > 0: a hit stalls instead of raising
        # per-site stream: the site name folds into the seed so streams
        # are independent — call interleaving across sites cannot shift
        # another site's Bernoulli sequence
        self.rng = random.Random(f"{seed}:{name}")
        self.rolls = 0
        self.injected = 0
        self.delayed = 0


class FaultPlan:
    """The process-global fault plan. ``configure(spec)`` arms it;
    ``configure(None)`` / ``configure("")`` disarms (tests MUST disarm
    in teardown — the plan is process state like TRACER)."""

    def __init__(self):
        # flowlint: unguarded -- armed/disarmed once at configure time (before the threads that read it); hot-path reads are a racy-but-monotone bool by design
        self.active = False
        # flowlint: unguarded -- the lock itself; bound once
        self._lock = threading.Lock()
        self._sites: dict[str, _Site] = {}  # guarded-by: _lock
        # flowlint: unguarded -- rebound only under configure (single caller at startup)
        self.spec = ""
        self.m_injected = REGISTRY.counter(
            "faults_injected_total",
            "flowchaos injected faults (label: site)")
        self.m_delayed = REGISTRY.counter(
            "faults_delayed_total",
            "flowchaos injected latency stalls (label: site) — delay "
            "sites slow a call instead of failing it")

    def configure(self, spec: Optional[str]) -> None:
        """Arm/disarm from a plan spec. Empty/None = off."""
        with self._lock:
            if not spec:
                self._sites = {}
                self.active = False
                self.spec = ""
                return
            sites, seed = parse_plan_full(spec)
            self._sites = {name: _Site(p, seed, name, delay)
                           for name, (p, delay) in sites.items()}
            self.spec = spec
            self.active = any(s.p > 0 for s in self._sites.values())

    def _roll(self, site: str) -> tuple[bool, float]:
        """One Bernoulli roll on the site's deterministic stream ->
        (hit, delay seconds). The roll discipline is identical for
        failure and latency sites — the delay only changes what a hit
        DOES, never the stream."""
        with self._lock:
            st = self._sites.get(site)
            if st is None or st.p <= 0.0:
                # p=0 sites still exist (the bench A/B runs the armed
                # path with p=0) but consume no roll — a zero-p site
                # must not perturb its own future stream
                return False, 0.0
            st.rolls += 1
            hit = st.rng.random() < st.p
            delay = st.delay
            if hit:
                if delay > 0.0:
                    st.delayed += 1
                else:
                    st.injected += 1
        if hit:
            if delay > 0.0:
                self.m_delayed.inc(site=site)
            else:
                self.m_injected.inc(site=site)
        return hit, delay

    def should_fail(self, site: str) -> bool:
        """One Bernoulli roll on the site's deterministic stream. Call
        guarded: ``if FAULTS.active and FAULTS.should_fail(...)``.
        Latency sites never FAIL — a hit there returns False (check()
        is where the stall happens)."""
        hit, delay = self._roll(site)
        return hit and delay <= 0.0

    def check(self, site: str) -> None:
        """Raise FaultInjected when the site's roll fails; SLEEP (the
        injected latency, outside the plan lock) when the site is a
        delay site — a slow dependency, not a dead one."""
        if not self.active:
            return
        hit, delay = self._roll(site)
        if not hit:
            return
        if delay > 0.0:
            time.sleep(delay)
            return
        raise FaultInjected(f"injected fault at {site} "
                            f"(plan {self.spec!r})")

    def snapshot(self) -> dict:
        """{site: {"p", "delay", "rolls", "injected", "delayed"}} —
        the bench artifact's injection record."""
        with self._lock:
            return {name: {"p": st.p, "delay": st.delay,
                           "rolls": st.rolls, "injected": st.injected,
                           "delayed": st.delayed}
                    for name, st in self._sites.items()}


FAULTS = FaultPlan()
