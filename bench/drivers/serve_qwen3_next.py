"""Serving cells of Qwen3-Next's hybrid block, served by
``launch.serve.ServeScheduler`` as one chip of an expert-parallel deployment
holds it.

Set-up, the warm start, the window and the stamps are the dense serving
driver's (``drivers/serve.py``, ``Cell``); this driver builds the hybrid
configuration and its weights from the seed, counts the work of the window
with the routed pairs the program reports (``work/qwen3_next.py``), and
checks against ``reference/qwen3_next.py``.

The check compares logits, on a sample of the requests served in the window
drawn from the seed, with the one served most tokens in it:

* ``max_logit_gap``: the widest gap by which a served token's logit lies
  below the float32 reference's best at its position, over every token the
  sampled requests were served;
* ``max_row_error``: the largest absolute difference, over the whole
  vocabulary, between the reference's logits and the program's at each
  sampled request's next position.  The program's row is one more step of
  the timed decode program on the slot pool the window left, so it carries
  whatever the served path kept in its cache and recurrent state;
* ``state_narrow_share``: of the nonzero entries of the sampled requests'
  Gated DeltaNet states in the slot pool the window left, the share that a
  bfloat16 holds exactly (the low 16 bits of the float32 zero).  The
  configuration keeps the recurrence in float32, where about 2**-16 of the
  entries read so; a state kept or rounded in bfloat16 reads 1.  Its effect
  on the logits is within twice the bfloat16 compute's own, too close to
  the other two readings' limits to show there.
"""
from __future__ import annotations

import math
from typing import Dict, List

import numpy as np

from bench import traffic as traffic_mod
from bench.drivers import serve as base
from bench.harness import Check
from bench.reference import qwen3_next as ref
from bench.work import qwen3_next as work


def arch_config(config: dict):
    """The program's ``ArchConfig`` for the configuration file: the
    published layer pattern and widths, the router over every published
    expert, the held share."""
    from repro.models.config import ArchConfig
    n = config["full_attention_interval"]
    dep = config["deployment"]
    return ArchConfig(
        name=config["name"], family="hybrid",
        d_model=config["hidden_size"],
        n_heads=config["num_attention_heads"],
        n_kv_heads=config["num_key_value_heads"],
        d_ff=config["intermediate_size"], vocab_size=config["vocab_size"],
        block_unit=("gdn+moe",) * (n - 1) + ("attn+moe",),
        n_repeats=config["num_hidden_layers"] // n,
        head_dim=config["head_dim"], qk_norm=True,
        rope_theta=float(config["rope_theta"]),
        rope_fraction=float(config["partial_rotary_factor"]),
        attn_output_gate=True, norm_eps=float(config["rms_norm_eps"]),
        tie_embeddings=config["tie_word_embeddings"],
        n_experts=dep["router_experts"],
        top_k=config["num_experts_per_tok"],
        experts_held=config["num_experts"],
        expert_offset=dep["expert_offset"],
        d_expert=config["moe_intermediate_size"],
        d_shared_expert=config["shared_expert_intermediate_size"],
        moe_shared_expert=True, moe_shared_gate=True, mlp_type="swiglu",
        gdn_k_heads=config["linear_num_key_heads"],
        gdn_v_heads=config["linear_num_value_heads"],
        gdn_k_head_dim=config["linear_key_head_dim"],
        gdn_v_head_dim=config["linear_value_head_dim"],
        gdn_conv=config["linear_conv_kernel_dim"],
        policy=config["architecture"]["policy"])


def make_weights(key, cfg):
    """Every weight of the model from one key, float32, on the device, in
    the program's parameter tree (blocks stacked over the periods).
    Matrices normal with variance 1/fan-in (the conv's fan-in is its
    width), norm scales 1 + 0.1 * normal, and the Gated DeltaNet decays and
    the embedding as the program's initialisation draws them."""
    import jax
    import jax.numpy as jnp
    from repro.models import model as M
    params = M.init_params(key, cfg)
    paths, tree = jax.tree_util.tree_flatten_with_path(params)
    keys = jax.random.split(jax.random.fold_in(key, 7), len(paths))
    out = []
    for k, (path, a) in zip(keys, paths):
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        if name.endswith("scale") or name.endswith("mixer/norm"):
            a = 1.0 + 0.1 * jax.random.normal(k, a.shape, jnp.float32)
        elif name != "embed" and not name.endswith(("A_log", "dt_bias")):
            a = jax.random.normal(k, a.shape, jnp.float32) \
                * a.shape[-2] ** -0.5
        out.append(a)
    return jax.tree_util.tree_unflatten(tree, out)


def narrow_share(states, rows):
    """Of the nonzero entries of ``states`` (float32 leaves, batch row at
    dim 1) in batch rows ``rows``, the share whose low 16 bits are zero."""
    import jax
    import jax.numpy as jnp
    hit = tot = 0
    for st in states:
        x = jnp.take(st, rows, axis=1).astype(jnp.float32)
        bits = jax.lax.bitcast_convert_type(x, jnp.uint32)
        nz = x != 0
        hit = hit + jnp.sum(nz & ((bits & 0xFFFF) == 0))
        tot = tot + jnp.sum(nz)
    return hit / jnp.maximum(tot, 1)


class Cell(base.Cell):
    def __init__(self, config: dict, traffic: dict, seed: int, devices,
                 seconds: float):
        super().__init__(config, traffic, seed, devices, seconds)
        self.dims = ref.Dims.of(config)
        self._rows: Dict[int, np.ndarray] = {}
        self._state_share = math.nan

    # ----------------------------------------------------------- set-up --
    def setup(self) -> None:
        import gc
        import jax
        import jax.numpy as jnp
        from repro.launch.serve import ServeScheduler
        s = self.serving
        self.cfg = arch_config(self.config)
        key = jax.random.PRNGKey(
            traffic_mod.rng_for(self.seed, 1).integers(2 ** 31))
        self.weights = jax.block_until_ready(jax.jit(
            make_weights, static_argnums=1)(key, self.cfg))
        self.sched = ServeScheduler(
            self.weights, self.cfg, max_seq=s["max_seq"],
            max_slots=s["slots"], temperature=0.0,
            cache_dtype=jnp.dtype(s["cache_dtype"]),
            pipeline_depth=s["pipeline_depth"])
        self.mix = traffic_mod.make(self.traffic, self.seed,
                                    self.cfg.vocab_size, s["max_seq"])
        self._next = 0
        for r in self.mix.warm:
            self._submit(r)
            self._step()
        gc.collect()

    # ---------------------------------------------------------- results --
    def window_flops(self) -> int:
        """Operations of the prompt and output tokens processed in the
        window, the routed experts by the pairs the decode ticks report."""
        total = 0
        for r in self.records.values():
            for k, t in enumerate(r.times):
                if not self.t_open <= t <= self.t_close:
                    continue
                p = len(r.prompt)
                total += (work.span_flops(self.config, 0, p) if k == 0
                          else work.span_flops(self.config, p + k - 1,
                                               p + k))
        pairs = sum(s.extra.get("moe_held_pairs", 0)
                    for s in self.window_stats("decode"))
        return total + work.pair_flops(self.config, pairs)

    # ------------------------------------------------------------ check --
    def sample(self) -> List[base.Served]:
        """The dense driver's sample, drawn from the requests still in their
        slots when the window closed where there are any (the row check
        needs their next position)."""
        resident = {u for u, r in self.records.items()
                    if r.state == "active"}
        if not resident:
            return super().sample()
        keep = self.records
        try:
            self.records = {u: r for u, r in keep.items() if u in resident}
            return super().sample()
        finally:
            self.records = keep

    def release(self) -> None:
        """Before the program's state goes: one more step of the timed
        decode program over the whole slot pool, keeping the logit rows of
        the requests the check samples."""
        import jax
        import jax.numpy as jnp
        sched = self.sched
        want = {r.uid for r in self.sample()}
        pos = np.zeros(sched.n_slots, np.int32)
        tok = np.zeros((sched.n_slots, 1), np.int32)
        for i, q in enumerate(sched.slots):
            if q is not None:
                pos[i], tok[i, 0] = q.pos, q.tokens[-1]
        logits, _, _ = sched._decode_fused(
            sched.params, sched.cache, jnp.asarray(pos), jnp.asarray(tok))
        V = self.cfg.vocab_size
        rows = [i for i, q in enumerate(sched.slots)
                if q is not None and q.uid in want]
        for i in rows:
            self._rows[sched.slots[i].uid] = np.asarray(
                jax.device_get(logits[i, 0, :V]))
        del logits
        states = [c["gdn"]["state"] for c in sched.cache["slots"]
                  if "gdn" in c]
        if rows and states:
            self._state_share = float(narrow_share(states,
                                                   jnp.asarray(rows)))
        super().release()

    def _compare(self, r: base.Served, quant: bool = False):
        """(gaps of the served tokens, row error at the next position) of
        one request; ``quant`` runs the float8 control in the program's
        place (its picks and its row)."""
        s = self.serving
        seq = np.concatenate([r.prompt, r.tokens])
        n = len(r.tokens)
        first = len(r.prompt) - 1            # position predicting tokens[0]
        rows = slice(first, first + n + 1)
        # a finished request's sequence can be one past max_seq
        pad = s["max_seq"] + 1
        exact = np.concatenate([np.asarray(b) for b in ref.logit_rows(
            self.weights, seq, rows, self.dims, pad_to=pad)])[:n + 1]
        if quant:
            other = np.concatenate([np.asarray(b) for b in ref.logit_rows(
                self.weights, seq, rows, self.dims, pad_to=pad,
                quant=True)])[:n + 1]
            picks, row = other[:n].argmax(-1), other[n]
        else:
            picks, row = r.tokens, self._rows.get(r.uid)
        got = exact[np.arange(n), picks]
        gaps = exact[:n].max(-1) - got
        err = (float(np.abs(row - exact[n]).max()) if row is not None
               else math.nan)
        return gaps, err

    def readings(self, quant: bool = False):
        """(max_logit_gap, max_row_error) over the sampled requests."""
        gaps, errs = [np.array([np.inf])], []
        for r in self.sample():
            g, e = self._compare(r, quant)
            gaps.append(g)
            errs.append(e)
        gap = float(np.concatenate(gaps[1:] or gaps).max())
        return gap, (max(errs) if errs else math.nan)

    def checks(self) -> List[Check]:
        lim = self.config["check"]
        gap, err = self.readings()
        return [Check("max_logit_gap", gap, lim["max_logit_gap"]),
                Check("max_row_error", err, lim["max_row_error"]),
                Check("state_narrow_share", self._state_share,
                      lim["state_narrow_share"])]
