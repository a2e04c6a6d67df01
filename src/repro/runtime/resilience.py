"""Serving resilience: deterministic fault injection, retry/shed policy,
health tracking, and the graceful-degradation ladder.

Occamy's system story is *latency tolerance* -- the fabric keeps computing
while individual transfers stall or straggle.  This module is the serving
translation of that discipline: every failure path in the serving stack
(``launch.serve``) is (a) injectable deterministically so it can be
tested and reproduced bit-for-bit, and (b) survivable per-request, so a
poisoned row never takes down its co-batched neighbours.

Pieces
------
* :class:`FaultSpec` / :class:`FaultPlan` -- a seeded registry of faults
  keyed by serving stage (``prefill / sample / quantize``).  Activation
  poisons (NaN/Inf) are injected with :func:`poison_rows` -- a single
  eager ``jnp.where`` on a host-built row mask, so injection adds **no
  host sync**; host-side faults raise
  :class:`InjectedFault`; stragglers sleep.  Every trigger is logged in
  ``plan.triggered`` so tests can assert exactly which faults fired.
* :class:`RetryPolicy` -- bounded exponential backoff for failed prefills
  and decode steps.
* :class:`HealthTracker` -- monotonic counters + a bounded event log,
  surfaced in ``summary()["health"]``.
* :class:`DegradationLadder` -- the ordered fallback rungs (quantized KV
  -> wide KV, sparse mask -> ``impl="ref"``) a driver walks down when
  health counters cross ``fail_threshold``.
* :func:`dequantize_cache` / :func:`corrupt_quant_scales` -- cache-level
  helpers for the ``kv_wide`` rung and the ``quantize``-stage fault.
"""
from __future__ import annotations

import dataclasses
import random as _random
import time
from collections import Counter
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import precision

# Where a driver calls a FaultPlan: ``prefill`` on an admission's logits,
# ``sample`` on a decode step's logits before it is sampled, ``quantize`` on
# the KV cache's scale leaves.
STAGES: Tuple[str, ...] = ("prefill", "sample", "quantize")

# Fault kinds: activation stages take nan/inf poisons plus host-side
# exception/straggler; the quantize stage corrupts cache scale leaves.
KINDS: Tuple[str, ...] = ("nan", "inf", "exception", "straggler")

_QUANT_LEAVES = frozenset({"k", "k_scale", "v", "v_scale"})


class InjectedFault(RuntimeError):
    """Raised by a FaultPlan ``exception`` fault (host-side failure)."""


class ShedError(RuntimeError):
    """Raised when admission control rejects a request (queue full)."""


# What a serving driver retries: the host-side failures this module injects.
# Every other exception is a fault of the program and propagates -- retrying
# it would turn a bug into a "failed request" and hide it.
RETRYABLE: Tuple[type, ...] = (InjectedFault,)


def poison_rows(x: jax.Array, rows: Sequence[int], kind: str) -> jax.Array:
    """Overwrite batch rows of ``x`` with NaN or Inf, rows elsewhere intact.

    Built as one eager ``jnp.where`` on a host-constructed ``(B,)`` mask
    broadcast over trailing dims -- dispatched asynchronously, no sync.
    """
    if not rows:
        return x
    fill = {"nan": jnp.nan, "inf": jnp.inf}[kind]
    mask = jnp.zeros((x.shape[0],), jnp.bool_).at[jnp.asarray(list(rows))].set(True)
    mask = mask.reshape((-1,) + (1,) * (x.ndim - 1))
    return jnp.where(mask, jnp.asarray(fill, x.dtype), x)


def corrupt_quant_scales(cache: Any, rows: Sequence[int], kind: str) -> Any:
    """Poison the per-row ``k_scale``/``v_scale`` leaves of a quantized KV
    cache (batch axis 1: leaves are ``(layers, B, ...)``).  Non-quantized
    caches poison the wide ``k``/``v`` leaves instead so the fault is
    observable under every cache configuration."""
    if not rows:
        return cache

    def walk(node):
        if isinstance(node, dict):
            keys = set(node)
            if keys & {"k_scale", "v_scale"}:
                out = dict(node)
                for name in ("k_scale", "v_scale"):
                    if name in out:
                        out[name] = _poison_axis1(out[name], rows, kind)
                return out
            if keys & {"k", "v"} and keys <= _QUANT_LEAVES | {"occupancy"}:
                out = dict(node)
                for name in ("k", "v"):
                    if name in out:
                        out[name] = _poison_axis1(out[name], rows, kind)
                return out
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            t = type(node)
            return t(walk(v) for v in node)
        return node

    return walk(cache)


def _poison_axis1(x: jax.Array, rows: Sequence[int], kind: str) -> jax.Array:
    if x.ndim < 2 or not jnp.issubdtype(x.dtype, jnp.floating):
        return x
    fill = {"nan": jnp.nan, "inf": jnp.inf}[kind]
    mask = jnp.zeros((x.shape[1],), jnp.bool_).at[jnp.asarray(list(rows))].set(True)
    mask = mask.reshape((1, -1) + (1,) * (x.ndim - 2))
    return jnp.where(mask, jnp.asarray(fill, x.dtype), x)


def dequantize_cache(cache: Any, dtype=jnp.float32) -> Any:
    """Rewrite a quantized KV cache as a wide one: every ``{k, k_scale, v,
    v_scale}`` dict collapses to ``{k, v}`` dequantized to ``dtype`` (other
    leaves -- e.g. routing ``occupancy`` -- pass through untouched).  The
    ``kv_wide`` degradation rung: after this, decoding proceeds with
    ``kv_quant=None`` semantics on the same logical contents."""

    def walk(node):
        if isinstance(node, dict):
            if {"k", "k_scale", "v", "v_scale"} <= set(node):
                out = {k: v for k, v in node.items()
                       if k not in _QUANT_LEAVES}
                out["k"] = precision.dequantize_rows(
                    node["k"], node["k_scale"], dtype)
                out["v"] = precision.dequantize_rows(
                    node["v"], node["v_scale"], dtype)
                return out
            return {k: walk(v) for k, v in node.items()}
        if isinstance(node, (list, tuple)):
            t = type(node)
            return t(walk(v) for v in node)
        return node

    return walk(cache)


@dataclasses.dataclass(frozen=True)
class FaultSpec:
    """One deterministic fault: fires at ``stage`` when every non-None
    selector matches (``uid`` the request, ``row`` the batch row, ``step``
    the decode step counter), at most ``times`` times total."""

    stage: str
    kind: str
    uid: Optional[int] = None
    row: Optional[int] = None
    step: Optional[int] = None
    times: int = 1
    delay_s: float = 0.05

    def __post_init__(self):
        if self.stage not in STAGES:
            raise ValueError(f"stage must be one of {STAGES}, got {self.stage!r}")
        if self.kind not in KINDS:
            raise ValueError(f"kind must be one of {KINDS}, got {self.kind!r}")
        if self.stage == "quantize" and self.kind in ("exception", "straggler"):
            raise ValueError("quantize faults corrupt scales: kind must be "
                             "'nan' or 'inf'")


class FaultPlan:
    """A deterministic, seeded registry of :class:`FaultSpec`\\ s.

    Drivers call :meth:`apply` at each stage boundary with the current
    activation and context; the plan either returns the activation
    untouched (no spec matches), returns it with matching rows poisoned,
    sleeps (straggler), or raises :class:`InjectedFault`.  ``triggered``
    logs every firing as ``(stage, kind, step, rows)`` so tests assert the
    exact fault set; :meth:`reset` re-arms all specs for an A/B re-run.
    """

    def __init__(self, specs: Sequence[FaultSpec] = ()):  # noqa: D401
        self.specs: List[FaultSpec] = list(specs)
        self.triggered: List[Tuple[str, str, Optional[int], Tuple[int, ...]]] = []
        self._remaining: Dict[int, int] = {
            i: s.times for i, s in enumerate(self.specs)}

    # -- construction helpers ------------------------------------------------
    @classmethod
    def single(cls, stage: str, kind: str, **kw) -> "FaultPlan":
        return cls([FaultSpec(stage=stage, kind=kind, **kw)])

    @classmethod
    def random(cls, seed: int, uids: Sequence[int], rate: float, *,
               stages: Sequence[str] = ("prefill", "sample"),
               kinds: Sequence[str] = ("nan", "inf", "exception"),
               max_step: int = 8) -> "FaultPlan":
        """Seeded random plan: each uid independently faults with
        probability ``rate`` at a random (stage, kind, step)."""
        rng = _random.Random(seed)
        specs = []
        for uid in uids:
            if rng.random() >= rate:
                continue
            stage = rng.choice(list(stages))
            kind = rng.choice(list(kinds))
            step = None if stage == "prefill" else rng.randrange(max_step)
            specs.append(FaultSpec(stage=stage, kind=kind, uid=uid, step=step))
        return cls(specs)

    def reset(self) -> None:
        self.triggered = []
        self._remaining = {i: s.times for i, s in enumerate(self.specs)}

    # -- matching ------------------------------------------------------------
    def _armed(self, stage: str, *,
               step: Optional[int]) -> List[Tuple[int, FaultSpec]]:
        return [(i, s) for i, s in enumerate(self.specs)
                if s.stage == stage and self._remaining.get(i, 0) > 0
                and (s.step is None or s.step == step)]

    def _rows_for(self, spec: FaultSpec, uids: Optional[Sequence[Optional[int]]],
                  nrows: int) -> List[int]:
        if spec.row is not None:
            return [spec.row] if spec.row < nrows else []
        if spec.uid is not None:
            if uids is None:
                return []
            return [r for r, u in enumerate(uids) if u == spec.uid]
        return list(range(nrows))

    # -- application ---------------------------------------------------------
    def apply(self, stage: str, x: jax.Array, *, step: Optional[int] = None,
              uids: Optional[Sequence[Optional[int]]] = None) -> jax.Array:
        """Stage hook for batched activations ``x`` of shape ``(B, ...)``."""
        for i, spec in self._armed(stage, step=step):
            if spec.kind == "straggler":
                self._remaining[i] -= 1
                self.triggered.append((stage, "straggler", step, ()))
                time.sleep(spec.delay_s)
                continue
            if spec.kind == "exception":
                self._remaining[i] -= 1
                self.triggered.append((stage, "exception", step, ()))
                raise InjectedFault(
                    f"injected {stage} exception (step={step}, uid={spec.uid})")
            rows = self._rows_for(spec, uids, int(x.shape[0]))
            if not rows:
                continue
            self._remaining[i] -= 1
            self.triggered.append((stage, spec.kind, step, tuple(rows)))
            x = poison_rows(x, rows, spec.kind)
        return x

    def apply_cache(self, cache: Any, *, step: Optional[int] = None,
                    uids: Optional[Sequence[Optional[int]]] = None,
                    nrows: int = 0) -> Any:
        """Quantize-stage hook: corrupt cache scale leaves for matching rows."""
        for i, spec in self._armed("quantize", step=step):
            rows = self._rows_for(spec, uids, nrows)
            if not rows:
                continue
            self._remaining[i] -= 1
            self.triggered.append(("quantize", spec.kind, step, tuple(rows)))
            cache = corrupt_quant_scales(cache, rows, spec.kind)
        return cache


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Bounded exponential backoff: attempt k (0-based retry index) sleeps
    ``min(base_delay_s * multiplier**k, max_delay_s)`` before re-running."""

    max_retries: int = 2
    base_delay_s: float = 0.0
    multiplier: float = 2.0
    max_delay_s: float = 1.0

    def delay(self, attempt: int) -> float:
        if self.base_delay_s <= 0:
            return 0.0
        return min(self.base_delay_s * self.multiplier ** attempt,
                   self.max_delay_s)

    def schedule(self) -> List[float]:
        return [self.delay(k) for k in range(self.max_retries)]


class HealthTracker:
    """Monotonic counters + a bounded event log for ``summary()['health']``."""

    MAX_EVENTS = 256

    def __init__(self):
        self.counters: Counter = Counter()
        self.events: List[Dict[str, Any]] = []

    def record(self, event: str, **detail) -> None:
        self.counters[event] += 1
        if len(self.events) < self.MAX_EVENTS:
            self.events.append({"event": event, **detail})

    def snapshot(self) -> Dict[str, Any]:
        return {"counters": dict(self.counters),
                "events": list(self.events)}


class DegradationLadder:
    """Ordered fallback rungs walked down as failures accumulate.

    Each :meth:`note_failure` increments a counter; every time it crosses a
    multiple of ``fail_threshold`` the next pending rung is returned for the
    driver to apply (``kv_wide`` -> dequantize the KV cache and decode wide,
    ``mask_ref`` -> rebuild the sparse attention spec with ``impl='ref'``).
    Rungs that don't apply to the driver's configuration are skipped at
    construction.
    """

    RUNGS: Tuple[str, ...] = ("kv_wide", "mask_ref")

    def __init__(self, rungs: Sequence[str], *, fail_threshold: int = 3):
        unknown = set(rungs) - set(self.RUNGS)
        if unknown:
            raise ValueError(f"unknown ladder rungs: {sorted(unknown)}")
        if fail_threshold < 1:
            raise ValueError("fail_threshold must be >= 1")
        self.pending: List[str] = [r for r in self.RUNGS if r in set(rungs)]
        self.applied: List[str] = []
        self.fail_threshold = int(fail_threshold)
        self.failures = 0

    @classmethod
    def for_serving(cls, *, kv_quant, attn_mask,
                    fail_threshold: int = 3) -> "DegradationLadder":
        rungs = []
        if kv_quant is not None:
            rungs.append("kv_wide")
        if attn_mask is not None and getattr(attn_mask, "impl", "ref") != "ref":
            rungs.append("mask_ref")
        return cls(rungs, fail_threshold=fail_threshold)

    def note_failure(self) -> Optional[str]:
        """Record one failure; return the next rung to apply when the
        running count crosses the threshold, else None."""
        self.failures += 1
        if self.pending and self.failures % self.fail_threshold == 0:
            rung = self.pending.pop(0)
            self.applied.append(rung)
            return rung
        return None

    def state(self) -> Dict[str, Any]:
        return {"failures": self.failures,
                "fail_threshold": self.fail_threshold,
                "applied": list(self.applied),
                "pending": list(self.pending)}
