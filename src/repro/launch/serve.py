"""Serving launcher: single-run and continuous-batching multi-tenant serving.

Two drivers share one base (:class:`_ServeBase`: dispatch-backend override,
fault hooks, spans, the degradation ladder), and both run the model as
whole-stack compiled programs -- ``model.prefill`` and ``model.decode_step``,
MoE layers included, on either dispatch backend ("gather" or the bcsr SpMM,
traced over its full grid).  The base takes the weights once and holds
their matrices in the policy's compute dtype (``model.compute_params``;
``summary()["weights"]`` counts the bytes), so no compiled step converts
them again:

* :class:`ServeLoop` -- the static-batch driver: one prefill over a fixed
  (B, S) prompt batch, then lockstep decode, one jit-compiled
  ``jit_decode_step`` per token, sampled on host.  Greedy (temperature 0)
  decoding is token-for-token identical to the plain prefill-then-decode
  loop.

* :class:`ServeScheduler` -- the continuous-batching frontend: a request
  queue with admission, join/evict *between decode steps* (finished or
  EOS'd sequences free their slot, queued prompts prefill into it), and
  per-request position / routing-occupancy / sampling state carried
  through the batch dim of the prefix-stable decode cache.  Decode steps
  run at a power-of-two *batch bucket* (``engine.batch_bucket``), so batch
  composition changes never retrace: compiled-step shapes are bounded by
  the batch buckets.  Per request the generated tokens are token-identical
  to running that request alone through a sequential :class:`ServeLoop`
  (enforced by tests/test_serve_scheduler.py) -- every per-row computation
  (attention at per-row positions, prefix-stable MoE occupancy, sampling
  keys) is independent of which neighbours share the batch.  Each decode
  tick is one donated program over the slot pool and one compiled sampler
  on the device, with one fetch of the token ids and health bits.

**Resilience** (``runtime.resilience``, tests/README.md "Resilience
contract"): both drivers take a deterministic ``fault_plan`` whose staged
hooks (prefill / sample / quantize) poison rows, corrupt quant scales,
raise, or straggle on demand.  The scheduler isolates failures per
request: cheap on-device ``isfinite`` health bits piggyback on the
existing per-step token fetch (zero NEW host syncs), a poisoned row is
moved to a FAILED state, its cache row scatter-blanked
(``model.blank_cache_row``) and its slot refilled -- co-batched survivors'
tokens stay bit-identical to a fault-free run (per-row independence, the
same law behind the batch-bucket contract).  Failed prefills and decode
steps retry under a bounded exponential-backoff ``RetryPolicy`` (faults
fire before any key split, and a decode step's retry samples again from
the logits its one forward made, so a retry reproduces the fault-free
step exactly); requests carry optional TTFT/total deadlines and the
admission queue is bounded with an explicit shed policy.  Accumulated
failures walk a ``DegradationLadder`` (quantized KV -> wide, sparse mask
-> ref); everything is surfaced in ``summary()["health"]``.

**Spans**: each timed phase is one :class:`StepStat` in ``stats`` and one
``serve.<phase>`` profiler annotation, opened and closed at the same two
points (``_ServeBase._span``), so a device trace shows what the host was
doing in each gap.  A scheduler tick is ``step``, holding ``admit`` (a
``prefill`` per admission), ``decode`` (the forward through the health
fetch, the device sampler inside it), ``writeback`` (the commit of the
slot pool that ``jit_decode_step`` took donated and updated in place --
KV cache, and for hybrid stacks the Gated DeltaNet conv tail and
recurrent state -- as the scheduler's) and ``sample`` (the per-row host
bookkeeping: append, position, evict, fail); ``step`` carries the tick's
host-sync count, and ``decode`` of a dropless expert share the step's
(token, held expert) pairs as ``moe_held_pairs``, fetched with the token
ids.

Example:
  PYTHONPATH=src python -m repro.launch.serve --arch gemma3-12b --smoke \
      --batch 4 --prompt-len 32 --gen 32
  PYTHONPATH=src python -m repro.launch.serve --arch llama4-scout-17b-a16e \
      --smoke --dispatch bcsr --gen 16 --continuous --requests 6
"""
from __future__ import annotations

import argparse
import collections
import contextlib
import dataclasses
import functools
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config, get_smoke
from repro.core.masks import AttnMaskSpec
from repro.core.precision import policy as precision_policy
from repro.kernels import engine
from repro.kernels.flash_attention import ops as flash_ops
from repro.models import model as M
from repro.models import moe
from repro.parallel import context as pctx
from repro.runtime import resilience as R


@dataclasses.dataclass
class StepStat:
    """One timed phase of the loop, the program's span record; ``extra``
    carries phase-specific detail (e.g. a decode step's batch bucket).
    ``start`` is the host monotonic clock at the span's open, so
    ``start + seconds`` is its close."""
    phase: str  # step|admit|prefill|decode|writeback|sample
    step: int           # decode step index (-1 for prefill)
    seconds: float
    tokens: int = 0
    extra: Dict[str, Any] = dataclasses.field(default_factory=dict)
    start: float = 0.0


def _percentiles_ms(seconds: List[float]) -> Dict[str, float]:
    """p50/p99/mean of a latency sample, in milliseconds.

    Hardened for the failure paths: an empty sample (every request faulted
    or was shed before its first token) returns zeros, and None / non-finite
    entries (unset latency marks) are dropped rather than propagated into
    the percentiles."""
    seconds = [s for s in (seconds or [])
               if s is not None and np.isfinite(s)]
    if not seconds:
        return {"p50": 0.0, "p99": 0.0, "mean": 0.0, "n": 0}
    a = np.asarray(seconds, np.float64) * 1e3
    return {"p50": float(np.percentile(a, 50)),
            "p99": float(np.percentile(a, 99)),
            "mean": float(a.mean()), "n": int(a.size)}


def _sampler_body(vocab: int, temperature: float):
    """The scheduler's per-row sampling math: vocab slice, then argmax or
    a categorical vmapped over a ``(B, 2)`` stack of per-request keys --
    bit-identical per row to the eager ``_sample_one`` with that row's key
    (the composition-independence law).  Returns ``(B,)`` int32."""
    if temperature > 0:
        def fn(logits, keys):
            lg = logits[:, :vocab] / temperature
            return jax.vmap(jax.random.categorical)(keys, lg).astype(
                jnp.int32)
    else:
        def fn(logits, keys):
            return jnp.argmax(logits[:, :vocab], axis=-1).astype(jnp.int32)
    return fn


@functools.lru_cache(maxsize=None)
def _sampler_health_jit(vocab: int, temperature: float):
    """The decode tick's sampler + per-row health bits, one compiled
    program, over a decode step's whole ``(B, S, V)`` logits: the last
    position is sliced inside the program (the device trace shows
    ``jit_sample_step``).

    Returns ``(tokens, finite)`` where ``finite[b]`` is the
    ``all(isfinite)`` reduction of row ``b``'s vocab slice -- the poison
    detector.  The scheduler fetches both in the SAME ``jax.device_get``
    it already spends on the token ids, so per-request isolation costs
    zero additional host syncs.  Greedy takes ``None`` for the key
    operand."""
    body = _sampler_body(vocab, temperature)

    def sample_step(logits, key):
        last = logits[:, -1]
        fin = jnp.all(jnp.isfinite(last[:, :vocab]), axis=-1)
        return body(last, key), fin
    return jax.jit(sample_step)


@functools.lru_cache(maxsize=None)
def _health_accum_jit(vocab: int):
    """Fold one decode step's last-position logits into a running per-row
    health mask, on device: ``acc & all(isfinite(row))``.  Dispatched (not
    fetched) per step, read back once at the end of the run -- the
    ``ServeLoop`` health path adds no per-step sync."""
    return jax.jit(lambda lg, acc: acc & jnp.all(
        jnp.isfinite(lg[:, :vocab]), axis=-1))


def _decode_program(cfg):
    """The fused one-token decode step, jitted under a stable name (the
    device trace shows ``jit_decode_step``).  Returns ``(logits, cache,
    held)``: ``held`` is the step's (token, held expert) pair count
    (``model.moe_held_pairs``), None where the configuration has no
    dropless expert share."""
    def decode_step(params, cache, pos, tokens):
        logits, new_cache = M.decode_step(params, cfg, cache, pos, tokens)
        return logits, new_cache, M.moe_held_pairs(cfg, new_cache)
    return jax.jit(decode_step)


def _put_rows(pool, rows, start):
    """``rows`` (a cache of the pool's structure, fewer batch rows) written
    into ``pool`` at batch row ``start``, each leaf cast to the pool's
    dtype; every other row is left as it was."""
    return jax.tree.map(
        lambda big, small: jax.lax.dynamic_update_slice_in_dim(
            big, small.astype(big.dtype), start, axis=1),
        pool, rows)


def _decode_inplace_program(cfg):
    """The scheduler's fused tick as one program that owns the slot pool,
    under the same stable name ``jit_decode_step``: ``(params, pool, pos,
    tokens, *, bucket) -> (logits, pool, held)``.  ``pool`` is donated and
    ``bucket`` static: the step runs on the pool's rows ``[0, bucket)`` and
    writes the new rows back inside the program, so the returned pool takes
    the donated one's buffers and rows ``bucket..`` come back bit for bit.
    Once dispatched, the pool passed in is gone."""
    def decode_step(params, pool, pos, tokens, bucket):
        cache = jax.tree.map(lambda a: a[:, :bucket], pool)
        logits, new_cache = M.decode_step(params, cfg, cache, pos, tokens)
        return (logits, _put_rows(pool, new_cache, 0),
                M.moe_held_pairs(cfg, new_cache))
    return jax.jit(decode_step, static_argnames="bucket",
                   donate_argnames="pool")


@functools.partial(jax.jit, donate_argnames="pool")
def _commit_row(pool, row_cache, slot):
    """An admission's write-back: the single-request cache ``row_cache``
    into row ``slot`` of the donated slot pool."""
    return _put_rows(pool, row_cache, slot)


def _weight_counts(given, served, cfg) -> Dict[str, int]:
    """``summary()["weights"]``: the bytes of the served tree's leaves held
    in the compute dtype and of all the others, and how many leaves
    ``M.compute_params`` cast from the tree it was given."""
    cd = jnp.dtype(precision_policy(cfg.policy).compute_dtype)
    leaves = jax.tree.leaves(served)
    return {"compute_bytes": sum(a.nbytes for a in leaves if a.dtype == cd),
            "wide_bytes": sum(a.nbytes for a in leaves if a.dtype != cd),
            "cast_leaves": sum(a.dtype != b.dtype for a, b in
                               zip(leaves, jax.tree.leaves(given)))}


class _ServeBase:
    """What the static-batch :class:`ServeLoop` and the continuous-batching
    :class:`ServeScheduler` share: the dispatch-backend override, the fault
    hooks, the spans and host-sync counter, and the degradation ladder."""

    def __init__(self, params, cfg, *, dispatch: Optional[str] = None,
                 temperature: float = 0.0, sample_seed: int = 3,
                 quantize_experts: Optional[str] = None,
                 kv_quant: Optional[str] = None,
                 attn_mask: Optional[AttnMaskSpec] = None,
                 fault_plan: Optional[R.FaultPlan] = None,
                 retry: Optional[R.RetryPolicy] = None,
                 fail_threshold: int = 3):
        self.params, self.cfg = params, cfg
        self.quantize_experts = quantize_experts
        self.kv_quant = kv_quant
        self.attn_mask = attn_mask
        # baseline for the attention-fallback counter surfaced in
        # summary()["timing"]: only fallbacks observed by THIS driver count
        self._fallback_base = flash_ops.fallback_count()
        if quantize_experts:
            # opt-in narrow expert FFN weights: one-time host quantization,
            # QuantTensor leaves then flow through the compiled step
            self.params = moe.quantize_model_experts(params, quantize_experts)
        # the matrices held in the compute dtype once, here, so no compiled
        # step converts the whole weight set again on every call
        given = self.params
        self.params = M.compute_params(given, cfg)
        self._weights = _weight_counts(given, self.params, cfg)
        self.backend = dispatch or cfg.moe_dispatch
        self.temperature = temperature
        self._sample_seed = sample_seed
        self._sample_key = jax.random.PRNGKey(sample_seed)
        self.stats: List[StepStat] = []
        # -- resilience state (runtime.resilience) --------------------------
        self.fault_plan = fault_plan
        self.retry = retry if retry is not None else R.RetryPolicy()
        self.health = R.HealthTracker()
        self.ladder = R.DegradationLadder.for_serving(
            kv_quant=kv_quant, attn_mask=attn_mask,
            fail_threshold=fail_threshold)
        self._row_uids: Optional[List[Optional[int]]] = None
        self._host_syncs = 0   # every wait on / fetch of a device value

    # -------------------------------------------------------------- spans --

    @contextlib.contextmanager
    def _span(self, phase: str, step: int, *, tokens: int = 0, **extra):
        """Time one phase: a ``serve.<phase>`` profiler annotation (on the
        device trace's clock) and a :class:`StepStat` appended to
        ``self.stats`` on a normal exit, both opened and closed at the same
        two points.  Yields the record, whose ``extra`` the body may fill.
        The annotation's name stays bare: request ids and the like go in
        ``extra``, never in the profiler's name."""
        with jax.profiler.TraceAnnotation(f"serve.{phase}"):
            st = StepStat(phase, step, 0.0, tokens=tokens, extra=extra,
                          start=time.monotonic())
            yield st
            st.seconds = time.monotonic() - st.start
        self.stats.append(st)

    def _sync(self, fetch, x):
        """``fetch(x)``, counted as one host sync: ``fetch`` is
        ``jax.block_until_ready``, ``jax.device_get``, ``np.asarray`` or
        ``int`` of a device value."""
        self._host_syncs += 1
        return fetch(x)

    # ---------------------------------------------------------- resilience --

    def _fault(self, stage: str, x, *, step: Optional[int] = None):
        """Fault-plan hook for a batched activation; identity w/o a plan."""
        if self.fault_plan is None:
            return x
        return self.fault_plan.apply(stage, x, step=step,
                                     uids=self._row_uids)

    def _fault_cache(self, cache, *, step: Optional[int] = None,
                     uids=None, nrows: int = 0):
        """Quantize-stage hook: corrupt cache scale rows per the plan."""
        if self.fault_plan is None:
            return cache
        return self.fault_plan.apply_cache(cache, step=step, uids=uids,
                                           nrows=nrows)

    def _note_failure(self):
        """Count one failure toward the degradation ladder; apply the rung
        it returns (if any) to this driver's live configuration."""
        rung = self.ladder.note_failure()
        if rung is not None:
            self._apply_rung(rung)

    def _apply_rung(self, rung: str):
        self.health.record("degrade", rung=rung)
        if rung == "kv_wide":
            # quantized KV -> wide f32 KV: rebuild the live cache without
            # scale leaves; subsequent prefills/steps see kv_quant=None
            if getattr(self, "cache", None) is not None:
                self.cache = R.dequantize_cache(self.cache, jnp.float32)
            self.kv_quant = None
        elif rung == "mask_ref":
            # sparse stream-walk attention -> the jnp reference path
            self.attn_mask = dataclasses.replace(self.attn_mask, impl="ref")

    # ------------------------------------------------------------- phases --

    @contextlib.contextmanager
    def _dispatch_ctx(self):
        """Trace-time backend override for the fused (in-jit) paths.

        Touches ONLY ``MOE_DISPATCH`` -- an ambient ``activation_specs``
        context (mesh, EP/combine layout constraints, dispatch groups) must
        survive into the trace, so this cannot re-enter that manager (which
        resets every global it does not receive)."""
        prev = pctx.MOE_DISPATCH
        pctx.MOE_DISPATCH = self.backend
        try:
            yield
        finally:
            pctx.MOE_DISPATCH = prev

    def _phase_summary(self) -> Dict[str, Any]:
        """Per-phase seconds / call counts of the prefill and decode spans,
        the attention-fallback count, and the health surface."""
        out: Dict[str, Any] = {}
        for phase in ("prefill", "decode"):
            ss = [s for s in self.stats if s.phase == phase]
            if ss:
                out[phase] = {"seconds": sum(s.seconds for s in ss),
                              "calls": len(ss)}
        out["timing"] = {"attention_ref_fallbacks":
                         flash_ops.fallback_count() - self._fallback_base}
        out["weights"] = dict(self._weights)
        # resilience surface: monotonic counters + bounded event log
        # (HealthTracker), the degradation-ladder position, and the exact
        # faults the plan fired (see tests/README.md "Resilience contract")
        out["health"] = {
            **self.health.snapshot(),
            "ladder": self.ladder.state(),
            "faults_triggered": (list(self.fault_plan.triggered)
                                 if self.fault_plan is not None else []),
        }
        return out


class ServeLoop(_ServeBase):
    """Batched greedy/temperature serving loop with KV caches.

    Parameters
    ----------
    params, cfg : the model.
    max_seq : static decode-cache capacity (prompt + generation).
    dispatch : MoE dispatch backend override ("gather" | "bcsr");
        default is the config's ``moe_dispatch`` field.
    temperature : 0 = greedy argmax, > 0 = categorical sampling.
    quantize_experts : narrow dtype name ("fp8_e4m3" | "fp8_e5m2" | "int8")
        to BlockQuant the expert FFN weights at construction
        (``moe.quantize_model_experts``); None (default) leaves the
        experts unquantized.
    kv_quant : narrow dtype name to store full-context KV caches as
        per-position narrow values + f32 scales (local ring buffers stay
        wide); None (default) keeps the wide cache bit-for-bit.
    fault_plan : optional ``resilience.FaultPlan`` whose staged hooks this
        loop calls at every prefill / sample / quantize boundary (identity
        when None).
    retry, fail_threshold : the resilience knobs shared with the scheduler
        (here the retry policy is only carried for ``summary()`` symmetry;
        the static-batch loop re-raises step failures -- per-request retry
        lives in :class:`ServeScheduler`).
    """

    def __init__(self, params, cfg, *, max_seq: int,
                 dispatch: Optional[str] = None,
                 temperature: float = 0.0, sample_seed: int = 3,
                 quantize_experts: Optional[str] = None,
                 kv_quant: Optional[str] = None,
                 attn_mask: Optional[AttnMaskSpec] = None,
                 fault_plan: Optional[R.FaultPlan] = None,
                 retry: Optional[R.RetryPolicy] = None,
                 fail_threshold: int = 3):
        super().__init__(params, cfg, dispatch=dispatch,
                         temperature=temperature, sample_seed=sample_seed,
                         quantize_experts=quantize_experts,
                         kv_quant=kv_quant, attn_mask=attn_mask,
                         fault_plan=fault_plan, retry=retry,
                         fail_threshold=fail_threshold)
        self.max_seq = max_seq
        self._decode_fused = _decode_program(cfg)
        self.cache = None
        self.pos: Optional[int] = None
        self.generated: List[jax.Array] = []
        # per-row health: a device-resident running isfinite mask,
        # accumulated per step (dispatch only) and fetched once per run
        self._health_dev: Optional[jax.Array] = None
        self.health_rows: Optional[np.ndarray] = None

    # ------------------------------------------------------------- phases --

    def prefill(self, prompts: jax.Array,
                embeddings: Optional[jax.Array] = None) -> jax.Array:
        """Run the prompt through the model, fill the decode cache, and
        emit the first generated token (B, 1).  Resets the generation
        state up front, so a second ``run`` starts clean."""
        self.generated = []
        with self._span("prefill", -1, tokens=int(np.prod(prompts.shape))):
            with self._dispatch_ctx():
                logits, cache, pos = M.prefill(
                    self.params, prompts, self.cfg, max_seq=self.max_seq,
                    embeddings=embeddings, kv_quant=self.kv_quant,
                    attn_mask=self.attn_mask)
            logits, cache = jax.block_until_ready((logits, cache))
            logits = self._fault("prefill", logits, step=-1)
            cache = self._fault_cache(cache, step=-1,
                                      nrows=int(prompts.shape[0]))
        self.cache, self.pos = cache, int(pos)
        self._health_dev = jnp.all(
            jnp.isfinite(logits[:, -1, : self.cfg.vocab_size]), axis=-1)
        nxt = self._sample(logits[:, -1])
        self.generated = [nxt]
        return nxt

    def _sample(self, last_logits: jax.Array) -> jax.Array:
        lg = last_logits[:, : self.cfg.vocab_size]
        if self.temperature > 0:
            self._sample_key, k = jax.random.split(self._sample_key)
            nxt = jax.random.categorical(k, lg / self.temperature)
        else:
            nxt = jnp.argmax(lg, axis=-1)
        return nxt[:, None].astype(jnp.int32)

    def decode_step(self) -> jax.Array:
        """Generate one token for every sequence in the batch."""
        if self.cache is None:
            raise RuntimeError("decode_step before prefill")
        step = len(self.generated) - 1
        pos = self.pos + step
        if pos >= self.max_seq:
            # XLA clamps the out-of-bounds dynamic_update_slice instead of
            # failing, which would silently overwrite the LAST cache slot
            # every further step -- garbage tokens, no error.  Refuse.
            raise RuntimeError(
                f"ServeLoop.decode_step: KV-cache overflow -- decode write "
                f"position {pos} >= max_seq {self.max_seq} "
                f"(prefill filled {self.pos}, this is generated token "
                f"{step + 2}). Raise max_seq or generate fewer tokens.")
        tok = self.generated[-1]
        self.cache = self._fault_cache(self.cache, step=step,
                                       nrows=int(tok.shape[0]))
        t0 = time.monotonic()
        with self._dispatch_ctx():
            logits, self.cache, _ = self._decode_fused(
                self.params, self.cache, jnp.asarray(pos, jnp.int32), tok)
        logits = self._fault("sample", logits, step=step)
        if self._health_dev is not None:
            # dispatched, never fetched here: the run-end fetch reads it
            self._health_dev = _health_accum_jit(self.cfg.vocab_size)(
                logits[:, -1], self._health_dev)
        t_b = time.monotonic()
        logits = jax.block_until_ready(logits)
        t_done = time.monotonic()
        self.stats.append(StepStat(
            "decode", step, t_done - t0, tokens=tok.shape[0],
            extra={"logits_wait_s": t_done - t_b}))
        nxt = self._sample(logits[:, -1])
        self.generated.append(nxt)
        return nxt

    def decode(self, n: int):
        for _ in range(n):
            self.decode_step()

    # -------------------------------------------------------------- drive --

    def run(self, prompts: jax.Array, gen: int,
            embeddings: Optional[jax.Array] = None,
            sample_key: Optional[jax.Array] = None) -> np.ndarray:
        """prefill + (gen - 1) decode steps; returns (B, gen) token ids.

        Every ``run`` starts from a *fresh* sampling key -- reseeded from
        the constructor's ``sample_seed`` (or ``sample_key`` when given) --
        so consecutive runs with ``temperature > 0`` are reproducible."""
        self.stats.clear()
        self._fallback_base = flash_ops.fallback_count()
        self._sample_key = (jax.random.PRNGKey(self._sample_seed)
                            if sample_key is None else sample_key)
        self._health_dev = None
        self.health_rows = None
        self.prefill(prompts, embeddings=embeddings)
        self.decode(gen - 1)
        if self._health_dev is not None:
            # the one health fetch of the run
            self.health_rows = np.asarray(self._health_dev)
            bad = int((~self.health_rows).sum())
            if bad:
                self.health.record("rows_poisoned", rows=int(bad))
        return np.asarray(jnp.concatenate(self.generated, axis=1))

    def summary(self) -> Dict[str, Any]:
        """Aggregate per-phase seconds / counts for the last ``run``; decode
        tok/s is over the blocked decode-step walls."""
        out = self._phase_summary()
        dec = out.get("decode")
        if dec and dec["seconds"] > 0:
            batch = self.generated[0].shape[0] if self.generated else 0
            out["decode"]["tok_per_s"] = batch * dec["calls"] / dec["seconds"]
        if self.health_rows is not None:
            out["health"]["rows_finite"] = self.health_rows.tolist()
        return out


# ---------------------------------------------------- continuous batching --

@dataclasses.dataclass
class Request:
    """One user request in the continuous-batching scheduler.

    The scheduler fills in the lifecycle fields: ``tokens`` (generated ids),
    ``latencies_s`` (wall seconds of the step that emitted each token --
    the prefill pass for token 0, the shared decode step after), ``slot``
    (the cache batch row while resident), ``pos`` (next cache write
    position), and the timing marks used for first-token latency.

    ``state`` walks ``queued -> active -> finished`` on the happy path;
    the resilience layer adds ``failed`` (poisoned row or exhausted prefill
    retries -- ``fail_reason`` says why) and ``shed`` (bounded-queue
    admission rejection or an expired deadline before residency).
    ``ttft_deadline_s`` / ``deadline_s`` are optional wall-clock budgets
    from submit time to first token / final token."""
    prompt: np.ndarray
    max_new_tokens: int
    eos_id: Optional[int] = None
    uid: int = -1
    tokens: List[int] = dataclasses.field(default_factory=list)
    latencies_s: List[float] = dataclasses.field(default_factory=list)
    slot: Optional[int] = None
    pos: int = 0
    done: bool = False
    submit_time: float = 0.0
    first_token_s: Optional[float] = None
    key: Optional[jax.Array] = None    # per-request sampling key chain
    state: str = "queued"              # queued|active|finished|failed|shed
    fail_reason: Optional[str] = None
    retries: int = 0
    ttft_deadline_s: Optional[float] = None
    deadline_s: Optional[float] = None

    @property
    def prompt_len(self) -> int:
        return int(np.asarray(self.prompt).size)


class ServeScheduler(_ServeBase):
    """Continuous-batching multi-tenant serving frontend.

    A queue of :class:`Request`\\ s is served by a fixed pool of cache
    *slots* (batch rows of one shared decode cache).  Between decode steps
    the scheduler **evicts** finished sequences (token budget reached or
    EOS) and **admits** queued prompts into the freed rows: each admission
    runs a single-request prefill (the same program as :class:`ServeLoop`'s)
    and scatters the resulting cache into the slot row
    -- attention KV, MoE routing occupancy, and recurrent state are all
    batch-row-indexed (see ``model.init_cache``), so neighbours are
    untouched.  Decode then advances *every* resident sequence one token in
    a single batched step at per-row positions.

    **Batch-bucket law.**  The decode step runs on cache rows
    ``[0, batch_bucket(highest occupied slot + 1))`` --
    ``engine.batch_bucket`` is the power-of-two stream-bucket law applied
    to the batch dim -- so the compiled decode-step shapes are bounded by
    the batch buckets, never one per occupancy pattern.
    Vacant rows inside the bucket still compute (their results are masked
    at sampling and their cache rows are fully overwritten at the next
    admission); per-row independence keeps them from perturbing residents.

    **Per-request determinism.**  Sampling state is per request (a key
    chain folded from ``sample_seed`` and the request uid), so a request's
    tokens do not depend on batch composition; at temperature 0 the
    generated tokens are token-identical to a sequential single-request
    :class:`ServeLoop` with the same ``max_seq``.

    **Sampling.**  Every decode tick samples on the device: one compiled
    program (``_sampler_health_jit``) over the step's logits, and one
    ``jax.device_get`` of the token ids and the per-row health bits.  An
    admission's first token is still sampled on host (``_sample_one``).

    **Commit point.**  The slot pool is donated to every program that
    writes it.  ``jit_decode_step`` takes the whole pool, decodes rows
    ``[0, bucket)`` and writes them back inside the program; the
    ``writeback`` span only makes the returned pool ``self.cache``.  An
    admission writes its row with one donated program after the poison
    gate.

    ``pipeline_depth`` accepts only 0, the one serving mode.
    """

    def __init__(self, params, cfg, *, max_seq: int, max_slots: int = 8,
                 dispatch: Optional[str] = None,
                 temperature: float = 0.0, sample_seed: int = 3,
                 batch_min_bucket: int = 1, cache_dtype=jnp.bfloat16,
                 pipeline_depth: int = 0,
                 quantize_experts: Optional[str] = None,
                 kv_quant: Optional[str] = None,
                 attn_mask: Optional[AttnMaskSpec] = None,
                 fault_plan: Optional[R.FaultPlan] = None,
                 retry: Optional[R.RetryPolicy] = None,
                 fail_threshold: int = 3,
                 max_queue: Optional[int] = None,
                 shed_policy: str = "reject",
                 clock=None):
        if pipeline_depth != 0:
            raise ValueError(
                f"ServeScheduler: pipeline_depth must be 0, got "
                f"{pipeline_depth!r}; decode ticks are not pipelined")
        super().__init__(params, cfg, dispatch=dispatch,
                         temperature=temperature, sample_seed=sample_seed,
                         quantize_experts=quantize_experts,
                         kv_quant=kv_quant, attn_mask=attn_mask,
                         fault_plan=fault_plan, retry=retry,
                         fail_threshold=fail_threshold)
        self.max_seq = max_seq
        self.batch_min_bucket = batch_min_bucket
        # allocate the slot pool at its own bucket so every step bucket,
        # clamped by the pool, is still a power of two
        self.n_slots = engine.batch_bucket(max_slots,
                                           minimum=batch_min_bucket)
        self.cache_dtype = cache_dtype
        self.cache = M.init_cache(cfg, self.n_slots, max_seq,
                                  dtype=cache_dtype, kv_quant=kv_quant)
        self.slots: List[Optional[Request]] = [None] * self.n_slots
        self.queue: Deque[Request] = collections.deque()
        self.finished: List[Request] = []
        self.failed: List[Request] = []
        self.shed: List[Request] = []
        if shed_policy not in ("reject", "drop_oldest"):
            raise ValueError("shed_policy must be 'reject' or 'drop_oldest'")
        self.max_queue = max_queue
        self.shed_policy = shed_policy
        # injectable time/sleep so deadline & backoff tests run on a fake
        # clock instead of wall time
        self._clock = clock if clock is not None else time.monotonic
        self._sleep = time.sleep
        self.step_idx = 0
        self._next_uid = 0
        self.batch_buckets: set = set()
        # the undonated step, for callers that keep the pool they pass
        self._decode_fused = _decode_program(cfg)
        self._decode_inplace = _decode_inplace_program(cfg)
        # (logits, advanced pool or None once committed, held) of a step
        # whose forward ran and whose tick has not finished
        self._advanced: Optional[Tuple[Any, Any, Any]] = None

    # -------------------------------------------------------------- admit --

    def submit(self, prompt, max_new_tokens: int,
               eos_id: Optional[int] = None,
               ttft_deadline_s: Optional[float] = None,
               deadline_s: Optional[float] = None) -> Request:
        """Queue a request.  Admission control happens here: a request whose
        prompt + generation budget cannot fit the cache is refused up front
        (its final token is sampled but never written, hence the ``- 1``),
        and a full bounded queue (``max_queue``) sheds per ``shed_policy``
        -- ``"reject"`` raises :class:`resilience.ShedError` at the caller,
        ``"drop_oldest"`` sheds the oldest queued request to make room.
        ``ttft_deadline_s`` / ``deadline_s`` bound submit->first-token /
        submit->completion wall time; expired requests are shed (queued) or
        failed (resident) at the next scheduler tick."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if max_new_tokens < 1:
            raise ValueError("submit: max_new_tokens must be >= 1")
        need = prompt.size + max_new_tokens - 1
        if need > self.max_seq:
            raise ValueError(
                f"submit: request needs {need} cache positions "
                f"({prompt.size} prompt + {max_new_tokens} generated - 1) "
                f"but max_seq is {self.max_seq}; it could never be served "
                "without a KV-cache overflow.")
        if self.max_queue is not None and len(self.queue) >= self.max_queue:
            if self.shed_policy == "reject":
                self.health.record("shed", reason="queue_full",
                                   uid=self._next_uid)
                raise R.ShedError(
                    f"submit: admission queue full ({len(self.queue)} >= "
                    f"max_queue {self.max_queue}); request rejected "
                    f"(shed_policy='reject')")
            # drop_oldest: the oldest *queued* (never-resident) request
            # yields its place to the newcomer
            self._shed(self.queue.popleft(), "queue_full_drop_oldest")
        req = Request(prompt=prompt, max_new_tokens=max_new_tokens,
                      eos_id=eos_id, uid=self._next_uid,
                      submit_time=self._clock(),
                      ttft_deadline_s=ttft_deadline_s,
                      deadline_s=deadline_s,
                      key=jax.random.fold_in(
                          jax.random.PRNGKey(self._sample_seed),
                          self._next_uid))
        self._next_uid += 1
        self.queue.append(req)
        return req

    def _sample_one(self, logits_row: jax.Array, req: Request) -> int:
        lg = logits_row[: self.cfg.vocab_size]
        if self.temperature > 0:
            req.key, k = jax.random.split(req.key)
            return self._sync(int, jax.random.categorical(
                k, lg / self.temperature))
        return self._sync(int, jnp.argmax(lg))

    def _finish_or_keep(self, req: Request, tok: int):
        if len(req.tokens) >= req.max_new_tokens or (
                req.eos_id is not None and tok == req.eos_id):
            self._evict(req)

    def _evict(self, req: Request):
        self.slots[req.slot] = None
        req.slot = None
        req.done = True
        req.state = "finished"
        self.finished.append(req)

    # -------------------------------------------------- failure lifecycle --

    def _fail(self, req: Request, reason: str, *, poisoned: bool = False):
        """Move a request to the FAILED terminal state.  A poisoned
        resident additionally gets its cache row scatter-blanked
        (``model.blank_cache_row``) so stale NaN/Inf state cannot leak into
        the admission that refills the slot; neighbouring rows -- and
        therefore every surviving request's tokens -- are untouched."""
        if req.slot is not None:
            slot = req.slot
            self.slots[slot] = None
            req.slot = None
            if poisoned:
                self.cache = M.blank_cache_row(self.cache, slot)
        req.done = True
        req.state = "failed"
        req.fail_reason = reason
        self.failed.append(req)
        self.health.record("request_failed", uid=req.uid, reason=reason)
        self._note_failure()

    def _shed(self, req: Request, reason: str):
        """Shed a queued (never-resident) request: terminal, no cache work."""
        req.done = True
        req.state = "shed"
        req.fail_reason = reason
        self.shed.append(req)
        self.health.record("shed", reason=reason, uid=req.uid)

    def _shed_expired(self, now: float):
        """Enforce deadlines at tick boundaries: queued requests past their
        TTFT or total deadline are shed; residents past their total
        deadline are failed (their row is clean -- no blanking needed)."""
        if self.queue:
            keep: Deque[Request] = collections.deque()
            while self.queue:
                r = self.queue.popleft()
                waited = now - r.submit_time
                if r.deadline_s is not None and waited > r.deadline_s:
                    self._shed(r, "deadline")
                elif (r.ttft_deadline_s is not None
                        and waited > r.ttft_deadline_s):
                    self._shed(r, "ttft_deadline")
                else:
                    keep.append(r)
            self.queue = keep
        for r in list(self.active):
            if (r.deadline_s is not None
                    and now - r.submit_time > r.deadline_s):
                self._fail(r, "deadline")

    def _prefill_into(self, req: Request, slot: int) -> bool:
        """Single-request prefill into cache row ``slot``, with bounded
        exponential-backoff retry (``RetryPolicy``).  Failed attempts --
        a retryable host-side exception (``resilience.RETRYABLE``) or
        non-finite first-token logits -- leave the shared cache and the
        request's key chain untouched (the health check runs BEFORE the
        scatter and before any key split), so a retry reproduces the
        fault-free prefill bit-for-bit.  Returns False once retries are exhausted
        (the request is moved to FAILED and the slot stays free)."""
        last_reason = "prefill_failed"
        for attempt in range(self.retry.max_retries + 1):
            if attempt:
                req.retries += 1
                self.health.record("retry", stage="prefill", uid=req.uid,
                                   attempt=attempt)
                delay = self.retry.delay(attempt - 1)
                if delay:
                    self._sleep(delay)
            try:
                ok = self._prefill_attempt(req, slot)
            except R.RETRYABLE as e:
                last_reason = f"prefill_error:{type(e).__name__}"
                self.health.record("prefill_error", uid=req.uid,
                                   error=type(e).__name__)
                self._note_failure()
                continue
            if ok:
                return True
            last_reason = "prefill_poisoned"
            self.health.record("prefill_poisoned", uid=req.uid)
            self._note_failure()
        self._fail(req, last_reason)
        return False

    def _prefill_attempt(self, req: Request, slot: int) -> bool:
        """One prefill try; False = non-finite logits (poisoned)."""
        self._row_uids = [req.uid]
        prompts = jnp.asarray(req.prompt[None, :])
        with self._span("prefill", self.step_idx, tokens=req.prompt_len,
                        uid=req.uid, slot=slot) as st:
            try:
                with self._dispatch_ctx():
                    logits, cache1, pos = M.prefill(
                        self.params, prompts, self.cfg,
                        max_seq=self.max_seq,
                        cache_dtype=self.cache_dtype,
                        kv_quant=self.kv_quant, attn_mask=self.attn_mask)
                logits, cache1 = self._sync(jax.block_until_ready,
                                            (logits, cache1))
                logits = self._fault("prefill", logits)
            finally:
                self._row_uids = None
        dt = st.seconds
        # the poison gate, BEFORE the scatter and before any key split:
        # a failed attempt leaves shared + per-request state untouched.
        # prefill already syncs, so this (vocab,) fetch adds no sync point.
        last_row = self._sync(np.asarray,
                              logits[0, -1, : self.cfg.vocab_size])
        if not np.isfinite(last_row).all():
            return False
        # one donated program writes every cache leaf's row `slot`: it
        # becomes this request, every other row's state is untouched
        self.cache = _commit_row(self.cache, cache1, slot)
        req.slot, req.pos = slot, self._sync(int, pos)
        req.state = "active"
        self.slots[slot] = req
        # quantize-stage faults corrupt the freshly scattered row's scale
        # leaves (detected as poison at this request's next sampled logits)
        self.cache = self._fault_cache(
            self.cache, uids=[r.uid if r is not None else None
                              for r in self.slots], nrows=self.n_slots)
        tok = self._sample_one(logits[0, -1], req)
        req.tokens.append(tok)
        req.latencies_s.append(dt)
        req.first_token_s = self._clock() - req.submit_time
        self._finish_or_keep(req, tok)
        return True

    def admit(self) -> List[Request]:
        """Prefill queued requests into free slots (lowest index first --
        keeps the occupied prefix, and so the step's batch bucket, small).
        A request whose prefill exhausts its retries is FAILED and the
        slot offered to the next queued request."""
        joined = []
        with self._span("admit", self.step_idx):
            while self.queue and None in self.slots:
                req = self.queue.popleft()
                if self._prefill_into(req, self.slots.index(None)):
                    joined.append(req)
        return joined

    # ------------------------------------------------------------- decode --

    @property
    def active(self) -> List[Request]:
        return [r for r in self.slots if r is not None]

    def decode_step(self) -> List[Tuple[Request, int]]:
        """One batched decode step over the occupied slot prefix; returns
        the (request, token) pairs emitted.

        Failure handling (the per-request isolation contract,
        tests/test_resilience.py): a retryable host-side exception
        (``resilience.RETRYABLE``) in the step retries under the
        ``RetryPolicy``.  No key split or token append happens before the
        failure can surface.  The step commits at its forward (the donated
        pool comes back advanced), so its retry samples again from the
        logits in hand and never runs the forward twice: the retry
        reproduces the fault-free step exactly.  A step that exhausts its
        retries leaves the advanced pool in ``self.cache`` and raises.  A
        *poisoned* row (non-finite sampled logits, detected by health bits
        piggybacked on the token fetch) fails only ITS request: the row is
        evicted and scatter-blanked, the token discarded, and every
        co-batched survivor keeps bit-identical tokens (per-row
        independence of attention / prefix-stable MoE / sampling)."""
        active = self.active
        if not active:
            return []
        for r in active:
            if r.pos >= self.max_seq:
                # admission control makes this unreachable for well-formed
                # requests; keep the guard -- the fused jit path cannot
                # host-check and would silently clamp the cache write
                raise RuntimeError(
                    f"ServeScheduler.decode_step: KV-cache overflow -- "
                    f"request {r.uid} at write position {r.pos} >= max_seq "
                    f"{self.max_seq}.")
        err: Optional[Exception] = None
        try:
            for attempt in range(self.retry.max_retries + 1):
                if attempt:
                    self.health.record("retry", stage="decode",
                                       step=self.step_idx, attempt=attempt)
                    delay = self.retry.delay(attempt - 1)
                    if delay:
                        self._sleep(delay)
                try:
                    return self._decode_attempt(active)
                except R.RETRYABLE as e:
                    # a degradation rung may rebuild the live cache
                    self._commit_advanced()
                    err = e
                    self.health.record("decode_error", step=self.step_idx,
                                       error=type(e).__name__)
                    self._note_failure()
            raise RuntimeError(
                f"ServeScheduler.decode_step: step {self.step_idx} failed "
                f"after {self.retry.max_retries} retries") from err
        finally:
            self._commit_advanced()
            self._advanced = None

    def _commit_advanced(self):
        """Make the pool a step's forward advanced ``self.cache``
        ahead of its write-back, keeping the step's logits for a retry:
        once the forward ran, the pool it was given is gone."""
        if self._advanced is not None and self._advanced[1] is not None:
            logits, pool, held = self._advanced
            self.cache, self._advanced = pool, (logits, None, held)

    def _decode_attempt(self, active: List[Request]) -> List[Tuple[Request, int]]:
        """One decode-step try over the occupied slot prefix.

        The step commits at its forward: ``jit_decode_step`` takes the
        whole slot pool donated and returns it with the step's rows
        written, held in ``self._advanced`` until the ``writeback`` span
        makes it ``self.cache`` (at once if the step fails after its
        forward, so a degradation rung rebuilds the live pool).  A retry of
        the same step samples again from those logits and never runs the
        forward twice."""
        hi = max(i for i, r in enumerate(self.slots) if r is not None) + 1
        bucket = engine.batch_bucket(hi, minimum=self.batch_min_bucket,
                                     cap=self.n_slots)
        self.batch_buckets.add(bucket)
        pos_vec = np.zeros(bucket, np.int32)
        tok_vec = np.zeros((bucket, 1), np.int32)
        for i, r in enumerate(self.slots[:bucket]):
            if r is not None:
                pos_vec[i] = r.pos
                tok_vec[i, 0] = r.tokens[-1]
        if self._advanced is None:
            # quantize-stage faults corrupt live scale rows mid-stream
            self.cache = self._fault_cache(
                self.cache, step=self.step_idx,
                uids=[r.uid if r is not None else None for r in self.slots],
                nrows=self.n_slots)
        self._row_uids = [r.uid if r is not None else None
                          for r in self.slots[:bucket]]
        with self._span("decode", self.step_idx, tokens=len(active),
                        batch_bucket=bucket, active=len(active)) as st:
            pool, toks, fin, held = self._decode_forward(pos_vec, tok_vec,
                                                         bucket)
            if held is not None:
                st.extra["moe_held_pairs"] = int(held)
        dt = st.seconds
        with self._span("writeback", self.step_idx):
            self.cache, self._advanced = pool, None
        emitted = []
        with self._span("sample", self.step_idx):
            for i, r in enumerate(self.slots[:bucket]):
                if r is None:
                    continue   # vacant bucket row: computed, masked out here
                if not fin[i]:
                    # poisoned row: fail + evict + blank THIS request only;
                    # survivors' rows were computed row-independently and
                    # are committed above bit-identically to a fault-free
                    # step
                    self._fail(r, f"poisoned:step{self.step_idx}",
                               poisoned=True)
                    continue
                tok = int(toks[i])
                r.tokens.append(tok)
                r.latencies_s.append(dt)
                r.pos += 1
                emitted.append((r, tok))
                self._finish_or_keep(r, tok)
        return emitted

    def _decode_forward(self, pos_vec, tok_vec, bucket: int):
        """The step's forward through the health fetch: returns
        ``(pool, toks, fin, held)``, ``pool`` the advanced slot pool,
        ``toks`` the sampled ids, ``fin`` the per-row isfinite bits and
        ``held`` the step's (token, held expert) pairs (None without a
        dropless expert share), the last three on the host.  The forward
        donates ``self.cache`` unless this step's forward already ran."""
        try:
            if self._advanced is None:
                with self._dispatch_ctx():
                    self._advanced = self._decode_inplace(
                        self.params, self.cache, jnp.asarray(pos_vec),
                        jnp.asarray(tok_vec), bucket=bucket)
            logits, pool, held = self._advanced
            if pool is None:    # committed when the retry began
                pool = self.cache
            # the sample hook fires BEFORE any per-request key split below,
            # so a sample-stage exception retries with key chains intact
            logits = self._fault("sample", logits, step=self.step_idx)
        finally:
            self._row_uids = None
        # sample on device (per-request key chains advance on host, exactly
        # as _sample_one's) and fetch the (bucket,) token ids PLUS the
        # per-row isfinite health bits in the single device_get the
        # scheduler cannot shed: EOS / eviction decisions need the values.
        key_arr = None      # greedy ignores the key operand
        if self.temperature > 0:
            keys, dummy = [], None
            for r in self.slots[:bucket]:
                if r is not None:
                    r.key, k = jax.random.split(r.key)
                    keys.append(k)
                else:   # vacant row: sampled then masked; any key works
                    if dummy is None:
                        dummy = jnp.zeros((2,), jnp.uint32)
                    keys.append(dummy)
            key_arr = jnp.stack(keys)
        toks, fin = _sampler_health_jit(
            self.cfg.vocab_size, float(self.temperature))(logits, key_arr)
        toks, fin, held = self._sync(jax.device_get, (toks, fin, held))
        return pool, toks, fin, held

    # -------------------------------------------------------------- drive --

    def step(self) -> List[Tuple[Request, int]]:
        """One scheduler tick: enforce deadlines, admit into freed slots,
        then decode one token for every resident sequence.  The tick's
        ``step`` record carries ``extra["host_syncs"]``, the host syncs
        made inside it."""
        syncs0 = self._host_syncs
        with self._span("step", self.step_idx) as st:
            self._shed_expired(self._clock())
            self.admit()
            out = self.decode_step()
            st.extra["host_syncs"] = self._host_syncs - syncs0
        self.step_idx += 1
        return out

    def has_work(self) -> bool:
        return bool(self.queue) or any(r is not None for r in self.slots)

    def run(self, max_steps: int = 1_000_000) -> Dict[int, np.ndarray]:
        """Drive until queue and slots drain (or ``max_steps`` ticks);
        returns {uid: generated token ids} over all finished requests."""
        steps = 0
        while self.has_work() and steps < max_steps:
            self.step()
            steps += 1
        return {r.uid: np.asarray(r.tokens, np.int32)
                for r in self.finished}

    def summary(self) -> Dict[str, Any]:
        """Aggregate serving stats: per-phase seconds, decode tok/s over
        *emitted* tokens, per-token and first-token latency
        percentiles, and the bucket accounting that bounds recompiles."""
        out = self._phase_summary()
        dec = out.get("decode")
        if dec and dec["seconds"] > 0:
            emitted = sum(s.tokens for s in self.stats if s.phase == "decode")
            out["decode"]["tokens"] = emitted
            out["decode"]["tok_per_s"] = emitted / dec["seconds"]
        reqs = self.finished + self.active
        lat = [s for r in reqs for s in r.latencies_s]
        out["token_latency_ms"] = _percentiles_ms(lat)
        out["first_token_ms"] = _percentiles_ms(
            [r.first_token_s for r in reqs if r.first_token_s is not None])
        out["requests"] = {"finished": len(self.finished),
                           "queued": len(self.queue),
                           "active": len(self.active),
                           "failed": len(self.failed),
                           "shed": len(self.shed),
                           "retries": sum(r.retries for r in
                                          self.finished + self.failed
                                          + self.active)}
        out["health"]["failed"] = [
            {"uid": r.uid, "reason": r.fail_reason} for r in self.failed]
        out["health"]["shed"] = [
            {"uid": r.uid, "reason": r.fail_reason} for r in self.shed]
        out["batch_buckets"] = sorted(self.batch_buckets)
        return out


def main():
    from repro.runtime.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--dispatch", choices=["config", "gather", "bcsr"],
                    default="config",
                    help="MoE dispatch backend (config = the arch's field)")
    ap.add_argument("--continuous", action="store_true",
                    help="drive the continuous-batching scheduler on a "
                         "synthetic multi-user trace instead of one static "
                         "batch")
    ap.add_argument("--requests", type=int, default=8,
                    help="--continuous: number of synthetic requests")
    ap.add_argument("--slots", type=int, default=4,
                    help="--continuous: resident slot pool size")
    ap.add_argument("--quantize-experts", default=None,
                    choices=["fp8_e4m3", "fp8_e5m2", "int8"],
                    help="BlockQuant the expert FFN weights to this narrow "
                         "dtype (per-output-channel f32 scales)")
    ap.add_argument("--kv-quant", default=None,
                    choices=["fp8_e4m3", "fp8_e5m2", "int8"],
                    help="store full-context KV caches as narrow values + "
                         "per-position f32 scales")
    ap.add_argument("--attn-mask", default="none",
                    choices=["none", "sliding", "local_global", "strided"],
                    help="route prefill attention through the block-sparse "
                         "stream walk: 'sliding' = local layers only (each "
                         "layer's own window), others additionally impose "
                         "the named long-context pattern on full-attention "
                         "layers")
    ap.add_argument("--attn-mask-impl", default="sparse",
                    choices=["sparse", "dense", "ref"],
                    help="masked-attention implementation (dense/ref are "
                         "the parity baselines)")
    args = ap.parse_args()

    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    key = jax.random.PRNGKey(0)
    params = M.init_params(key, cfg)
    max_seq = args.prompt_len + args.gen + (
        cfg.frontend_tokens if cfg.frontend != "none" else 0)

    dispatch = None if args.dispatch == "config" else args.dispatch
    attn_mask = None
    if args.attn_mask != "none":
        pattern = None if args.attn_mask == "sliding" else args.attn_mask
        attn_mask = AttnMaskSpec(local=True, pattern=pattern,
                                 impl=args.attn_mask_impl)

    if args.continuous:
        rng = np.random.default_rng(0)
        sched = ServeScheduler(
            params, cfg, max_seq=max_seq, max_slots=args.slots,
            dispatch=dispatch, temperature=args.temperature,
            quantize_experts=args.quantize_experts,
            kv_quant=args.kv_quant, attn_mask=attn_mask)
        for _ in range(args.requests):
            plen = int(rng.integers(max(2, args.prompt_len // 2),
                                    args.prompt_len + 1))
            sched.submit(rng.integers(0, cfg.vocab_size, plen),
                         int(rng.integers(max(2, args.gen // 2),
                                          args.gen + 1)))
        gen = sched.run()
        s = sched.summary()
        dec = s.get("decode", {"seconds": 0.0, "calls": 0})
        print(f"served {len(gen)} requests in {sched.step_idx} steps "
              f"({dec.get('tok_per_s', 0.0):.1f} decode tok/s)")
        lat = s["token_latency_ms"]
        print(f"per-token latency: p50 {lat['p50']:.1f} ms, "
              f"p99 {lat['p99']:.1f} ms over {lat['n']} tokens")
        print(f"batch buckets: {s['batch_buckets']}")
        for uid in sorted(gen)[:2]:
            print(f"  [{uid}] {gen[uid][:16].tolist()}")
        return

    prompts = jax.random.randint(jax.random.PRNGKey(1),
                                 (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    emb = None
    if cfg.frontend != "none":
        emb = 0.02 * jax.random.normal(
            jax.random.PRNGKey(2),
            (args.batch, cfg.frontend_tokens, cfg.d_model))

    loop = ServeLoop(
        params, cfg, max_seq=max_seq, dispatch=dispatch,
        temperature=args.temperature,
        quantize_experts=args.quantize_experts, kv_quant=args.kv_quant,
        attn_mask=attn_mask)
    gen = loop.run(prompts, args.gen, embeddings=emb)
    s = loop.summary()

    pf = s["prefill"]
    print(f"prefill: {pf['seconds']*1e3:.1f} ms for "
          f"{args.batch}x{args.prompt_len}")
    dec = s.get("decode", {"seconds": 0.0, "calls": 0})  # --gen 1: no steps
    print(f"decode:  {dec['seconds']*1e3:.1f} ms for {dec['calls']} steps "
          f"({dec.get('tok_per_s', 0.0):.1f} tok/s)")
    print("sample generations (token ids):")
    for b in range(min(args.batch, 2)):
        print(f"  [{b}] {gen[b, :16].tolist()}")


if __name__ == "__main__":
    main()
