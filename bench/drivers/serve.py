"""Serving cells: a dense decoder served by ``launch.serve.ServeScheduler``.

Set-up makes the weights on the device from the seed in one jitted call, in
the type the program keeps them (float32), builds the scheduler with the
configuration's serving options, and warms every shape the window uses by
submitting the warm-start requests one a tick: an admission into every
slot and each decode batch bucket.  The window drives the scheduler's own
tick, ``ServeScheduler.step()``, with a span around each, and stamps each
token when it was made.

The check runs after the window on a sample of the requests served in it,
drawn from the seed, with the one served most tokens in it: the plain
float32 reference (``reference/decoder.py``) is run over each prompt with
the tokens served, and the number compared is the widest gap by which a
served token's logit lies below the reference's best at that position.
"""
from __future__ import annotations

import dataclasses
import gc
import time
from typing import Dict, List, Optional

import numpy as np

from bench import traffic as traffic_mod
from bench.harness import Check
from bench.reference import decoder as ref
from bench.work import decoder as work

CLOCK = time.monotonic


def arch_config(config: dict):
    """The program's ``ArchConfig`` for a published dense config."""
    from repro.models.config import ArchConfig
    a = config["architecture"]
    return ArchConfig(
        name=config["name"], family="dense", d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        block_unit=("attn",), n_repeats=config["num_hidden_layers"],
        head_dim=config["head_dim"], qk_norm=a["qk_norm"],
        mlp_type=a["mlp"], rope_theta=float(config["rope_theta"]),
        norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=config["tie_word_embeddings"], policy=a["policy"])


def make_weights(key, dims: ref.Dims, vocab_rows: int):
    """Every weight of the model from one key, float32, on the device.

    Layout: ``embed`` (vocab_rows, d), ``final_norm`` (d,), and ``layers``
    stacked over the layers: ``ln1``/``ln2`` (d,), ``wq`` (d, H*hd), ``wk``
    and ``wv`` (d, K*hd), ``wo`` (H*hd, d), ``q_norm``/``k_norm`` (hd,),
    ``w_gate``/``w_up`` (d, ff), ``w_down`` (ff, d).  Projections are
    normal with variance 1/fan-in; norm scales 1 + 0.1 * normal, so that the
    comparison sees every one of them."""
    import jax
    import jax.numpy as jnp
    L, d, H, K, hd, ff = (dims.layers, dims.d, dims.heads, dims.kv_heads,
                          dims.head_dim, dims.ff)
    shapes = {"ln1": (L, d), "wq": (L, d, H * hd), "wk": (L, d, K * hd),
              "wv": (L, d, K * hd), "wo": (L, H * hd, d), "q_norm": (L, hd),
              "k_norm": (L, hd), "ln2": (L, d), "w_gate": (L, d, ff),
              "w_up": (L, d, ff), "w_down": (L, ff, d)}
    keys = jax.random.split(key, len(shapes) + 2)
    layers = {}
    for k, (name, shp) in zip(keys, sorted(shapes.items())):
        z = jax.random.normal(k, shp, jnp.float32)
        layers[name] = (1.0 + 0.1 * z if len(shp) == 2
                        else z * shp[1] ** -0.5)
    return {"embed": jax.random.normal(keys[-2], (vocab_rows, d),
                                       jnp.float32) * d ** -0.5,
            "final_norm": 1.0 + 0.1 * jax.random.normal(keys[-1], (d,)),
            "layers": layers}


def program_params(w) -> dict:
    """The same arrays in the program's parameter tree (no copy)."""
    lw = w["layers"]
    block = {"ln1": {"scale": lw["ln1"]}, "ln2": {"scale": lw["ln2"]},
             "attn": {"wq": lw["wq"], "wk": lw["wk"], "wv": lw["wv"],
                      "wo": lw["wo"], "q_norm": {"scale": lw["q_norm"]},
                      "k_norm": {"scale": lw["k_norm"]}},
             "ffn": {"w_gate": lw["w_gate"], "w_up": lw["w_up"],
                     "w_down": lw["w_down"]}}
    return {"embed": w["embed"], "final_norm": {"scale": w["final_norm"]},
            "blocks": (block,)}


@dataclasses.dataclass
class Served:
    """The harness's record of one request."""
    uid: int
    prompt: np.ndarray
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: Optional[np.ndarray] = None
    state: str = "queued"


class Cell:
    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 seconds: float):
        self.config, self.traffic, self.seed = config, traffic, seed
        self.devices, self.seconds = devices, seconds
        self.serving = config["serving"]
        self.dims = ref.Dims.of(config)
        self.records: Dict[int, Served] = {}
        self._live = {}                 # uid -> the scheduler's Request
        self.t_open = self.t_close = None

    # ----------------------------------------------------------- set-up --
    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        from repro.launch.serve import ServeScheduler
        s = self.serving
        self.cfg = arch_config(self.config)
        key = jax.random.PRNGKey(
            traffic_mod.rng_for(self.seed, 1).integers(2 ** 31))
        self.weights = jax.block_until_ready(jax.jit(
            make_weights, static_argnums=(1, 2))(
                key, self.dims, self.cfg.padded_vocab))
        self.sched = ServeScheduler(
            program_params(self.weights), self.cfg, max_seq=s["max_seq"],
            max_slots=s["slots"], temperature=0.0,
            cache_dtype=jnp.dtype(s["cache_dtype"]),
            pipeline_depth=s["pipeline_depth"])
        self.mix = traffic_mod.make(self.traffic, self.seed,
                                    self.cfg.vocab_size, s["max_seq"])
        self._next = 0
        # the warm start, one request a tick: each admission and every
        # decode batch bucket the window uses
        for r in self.mix.warm:
            self._submit(r)
            self._step()
        gc.collect()

    def _submit(self, r) -> None:
        q = self.sched.submit(r.prompt, r.max_new_tokens)
        self.records[q.uid] = Served(q.uid, r.prompt)
        self._live[q.uid] = q

    def _step(self) -> None:
        """One scheduler tick, ``ServeScheduler.step()``; each token is
        stamped when it was made: an admission's first token by the
        scheduler's clock, a decode step's when the tick returns."""
        import jax
        with jax.profiler.TraceAnnotation("bench.step"):
            emitted = self.sched.step()
        t = CLOCK()
        for uid, q in self._live.items():
            rec = self.records[uid]
            if not rec.times and q.first_token_s is not None:
                rec.times.append(q.submit_time + q.first_token_s)
        for q, _ in emitted:
            self.records[q.uid].times.append(t)
        self._settle()

    def _settle(self) -> None:
        """Close the records of requests that ended; once the window is
        open, their client sends its next request at once."""
        for uid, q in list(self._live.items()):
            if q.state in ("finished", "failed", "shed"):
                rec = self.records[uid]
                rec.state = q.state
                rec.tokens = np.asarray(q.tokens, np.int32)
                del self._live[uid]
                if self.t_open is not None:
                    r = self.mix.requests[self._next % len(self.mix.requests)]
                    self._next += 1
                    self._submit(r)

    # ----------------------------------------------------------- window --
    def run(self, seconds: float, tracer) -> None:
        self.t_open = t0 = CLOCK()
        self.n_stats0 = len(self.sched.stats)
        if tracer is not None:
            tracer.arm(t0 + seconds)
        while CLOCK() - t0 < seconds:
            if tracer is not None:
                tracer.tick(CLOCK())
            self._step()
        if tracer is not None:
            tracer.finish()
        self.t_close = CLOCK()
        self.n_stats1 = len(self.sched.stats)
        for uid, q in self._live.items():
            rec = self.records[uid]
            rec.tokens = np.asarray(q.tokens, np.int32)
            rec.state = q.state

    # ---------------------------------------------------------- results --
    def in_window(self) -> List[Served]:
        """Requests served a token in the window (a closed loop's requests
        outlive it)."""
        a, b = self.t_open, self.t_close
        return [r for r in self.records.values()
                if any(a <= t <= b for t in r.times)]

    def token_times(self) -> List[float]:
        return [t for r in self.records.values() for t in r.times
                if self.t_open <= t <= self.t_close]

    def itl_s(self) -> List[float]:
        """Every gap between consecutive tokens of one request that ends
        inside the window, over all requests."""
        return [b - a for r in self.records.values()
                for a, b in zip(r.times, r.times[1:])
                if self.t_open <= b <= self.t_close]

    def window_stats(self, phase: str):
        return [s for s in self.sched_stats[self.n_stats0:self.n_stats1]
                if s.phase == phase]

    def end_to_end(self) -> dict:
        span = self.t_close - self.t_open
        return {"output_tok_per_s": len(self.token_times()) / span,
                "itl_p95_ms": 1e3 * float(np.percentile(self.itl_s(), 95))}

    def window_flops(self) -> int:
        """Model FLOPs of the prompt and output tokens processed in the
        window."""
        total = 0
        for r in self.records.values():
            for k, t in enumerate(r.times):
                if not self.t_open <= t <= self.t_close:
                    continue
                p = len(r.prompt)
                total += (work.span_flops(self.config, 0, p) if k == 0
                          else work.span_flops(self.config, p + k - 1,
                                               p + k))
        return total

    def counts(self):
        reqs = self.in_window()
        return len(reqs), sum(r.state in ("failed", "shed") for r in reqs)

    # ------------------------------------------------------------ check --
    def release(self) -> None:
        """Free the program's state (cache, scheduler) before the reference
        runs; the benchmark's weights stay."""
        self.sched_stats = list(self.sched.stats)
        self.sched = None
        self._live = {}
        gc.collect()

    def sample(self) -> List[Served]:
        served = [r for r in self.in_window()
                  if r.tokens is not None and len(r.tokens)]
        if not served:
            return []
        n = self.config["check"]["requests"]
        longest = max(served, key=lambda r: (len(r.tokens), -r.uid))
        rest = [r for r in served if r is not longest]
        rng = traffic_mod.rng_for(self.seed, 2)
        pick = rng.choice(len(rest), size=min(n - 1, len(rest)),
                          replace=False) if rest else []
        return [longest] + [rest[i] for i in sorted(pick)]

    def gaps(self, control: bool = False) -> np.ndarray:
        return np.concatenate([
            ref.served_gaps(self.weights, r.prompt, r.tokens, self.dims,
                            pad_to=self.serving["max_seq"], control=control)
            for r in self.sample()] or [np.array([np.inf])])

    def checks(self) -> List[Check]:
        return [Check("max_logit_gap", float(self.gaps().max()),
                      self.config["check"]["max_logit_gap"])]
