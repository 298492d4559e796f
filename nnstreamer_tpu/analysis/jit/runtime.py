"""Runtime half of jitcheck: the compile-stability monitor.

The static passes predict WHERE compilation may happen (the jit-site
map, bucketed by CompileCache ``kind``); the runtime half observes what
actually happened — per-element ``jit_hits`` / ``jit_misses`` /
``jit_prewarmed`` / ``jit_recompiles`` counters plus
``jax.monitoring`` compile events — and
``check_against_static`` closes the contract:

* steady-state recompiles == 0 — a warmed process serving the same
  traffic must never compile on the frame path again;
* observed signatures ⊆ statically predicted — every CompileCache
  ``kind`` that recorded a signature must correspond to a jit
  construction the static scan saw (a kind the scan can't see means
  the model is unhooked, the gate's version of vacuous coverage).

``tools/jit_stability.py`` (``make jit-stability``) drives the builtin
corpus through two passes and applies exactly this check.
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, Set

JIT_STAT_KEYS = ("jit_recompiles", "jit_misses", "jit_hits",
                 "jit_prewarmed")


def jit_stat_snapshot(pipeline: Any) -> Dict[str, Dict[str, int]]:
    """Per-element jit counters for every element that has any (filter
    backends and fused segments), from one consistent stats() pass."""
    out: Dict[str, Dict[str, int]] = {}
    for name, snap in pipeline.stats().items():
        row = {k: int(snap[k]) for k in JIT_STAT_KEYS if k in snap}
        if row:
            out[name] = row
    return out


def steady_recompiles(snapshot: Dict[str, Dict[str, int]]) -> int:
    """Frame-path compilations in the window the snapshot covers: a
    filter's post-warmup signature compiles plus a fused segment's
    program-cache misses. Both must be zero once warm."""
    return sum(row.get("jit_recompiles", 0) + row.get("jit_misses", 0)
               for row in snapshot.values())


class CompileEventMonitor:
    """Counts jax.monitoring compile events process-wide. jax only
    offers clear-all for listeners, so ``install()`` is one-way —
    ``reset()`` rebases the count instead of unregistering."""

    def __init__(self) -> None:
        self._count = 0
        self._base = 0
        self.events: Dict[str, int] = {}

    def _on_event(self, event: str, **kwargs: Any) -> None:
        if "compil" in event:
            self._count += 1
            self.events[event] = self.events.get(event, 0) + 1

    def install(self) -> "CompileEventMonitor":
        from jax import monitoring
        monitoring.register_event_listener(self._on_event)
        monitoring.register_event_duration_secs_listener(
            lambda event, duration, **kw: self._on_event(event))
        return self

    def reset(self) -> None:
        self._base = self._count

    @property
    def count(self) -> int:
        return self._count - self._base


@dataclass
class StabilityResult:
    steady_recompiles: int
    observed_kinds: Set[str] = field(default_factory=set)
    static_kinds: Set[str] = field(default_factory=set)
    problems: list = field(default_factory=list)

    @property
    def ok(self) -> bool:
        return not self.problems

    def __str__(self) -> str:
        status = "ok" if self.ok else "BROKEN"
        return (f"jit-stability {status}: steady recompiles="
                f"{self.steady_recompiles}, observed kinds="
                f"{sorted(self.observed_kinds)} ⊆ static "
                f"{sorted(self.static_kinds)}"
                + ("".join(f"\n  {p}" for p in self.problems)))


def check_against_static(static: Any,
                         observed_kinds: Iterable[str],
                         steady: int,
                         strict: bool = True) -> StabilityResult:
    """The static↔runtime contract. ``static`` is a JitReport (or any
    object with ``jit_site_kinds``) or a plain iterable of kind names;
    ``observed_kinds`` is what CompileCache recorded; ``steady`` is the
    second-pass recompile count. Raises AssertionError with the full
    breakdown when strict (the gate path), else returns the result."""
    kinds = getattr(static, "jit_site_kinds", None)
    static_kinds = set(kinds) if kinds is not None else set(static)
    observed = set(observed_kinds)
    result = StabilityResult(steady_recompiles=int(steady),
                             observed_kinds=observed,
                             static_kinds=static_kinds)
    if steady:
        result.problems.append(
            f"{steady} compilation(s) on the frame path of a warmed "
            "process — the compile cache is not holding steady state")
    extra = observed - static_kinds
    if extra:
        result.problems.append(
            f"observed compile kind(s) {sorted(extra)} have no "
            "statically predicted jit site — the static scan does not "
            "see the code that compiled them")
    if strict and result.problems:
        raise AssertionError(str(result))
    return result
