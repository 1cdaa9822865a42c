"""Deadline-aware anytime serving of the big-model configs.

Continuous batching + Zygarde imprecise computation in one jitted
``lax.scan``: every step the engine admits released requests into free
batch slots (priority-ordered by the paper's zeta_I — Eq. 7 — or by EDF),
runs ONE batched :func:`repro.models.anytime.unit_decode_step` over all
slots, and picks a per-request *depth* for accounting:

* ``policy="anytime"`` — the margin utility test
  (:func:`repro.models.anytime.select_depth` over the per-unit exit-head
  margins, knobs ``exit_thr``/``use_exit_thr``) proposes a depth; a
  deadline cap (greedy per-token latency budget) and the Eq. 7 energy
  gate (``eta * energy >= E_opt``) can force it down to the mandatory
  prefix; the result is clamped to ``[mandatory, U]``.
* ``policy="edf"`` — fixed full depth (the precise-computation baseline).
* ``policy="edf-m"`` — fixed mandatory depth (maximal imprecision).

Step latency is the continuous-batching cost ``t_base + unit_time *
max(depth over active slots)`` — the whole batch waits for its deepest
request, which is exactly why per-request depth control beats fixed-depth
EDF under tight deadlines (``examples/anytime_serve.py``).  Energy flows
through a capacitor fed by a :class:`repro.core.energy.Harvester` power
trace; when the store cannot cover the platform base cost the step
brownouts (no compute, time still passes) — the intermittent-power
regime the zeta_I gate exists for.

Mechanics reused from the fleet substrate: a pure pytree
:class:`AnytimeCarry` stepped by a closed-over transition (``core/step.py``
style), checkpointable segmented scans (``run(..., n_segments=, hook=)``
— bit-exact for any segmentation, hooks may retune knobs between
segments), ``mesh=`` sharding of the decode state via
:func:`repro.launch.sharding.state_specs`, and an optional
:class:`repro.telemetry.Telemetry` fold (depth histogram, deadline
slack, admission/retire counters) compiled out when disabled.

The exit decision is *propagated* (CALM-style): an early-exited token is
fed back and the KV/recurrent state is still built by the full stack, so
depth is an accounting (time/energy) construct while the physical batch
step stays shape-static.  Agreement of every emitted token with the
full-depth argmax is tracked per request — the accuracy side of the
score.  Knobs are dynamic arguments (:class:`AnytimeKnobs`), so
``repro.adapt.tune`` can vmap thousands of candidate threshold/E_opt
settings over one compiled engine (:mod:`repro.adapt.anytime`).
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from ..core import energy as EN
from ..core import policy as POL
from ..launch import sharding as SH
from ..models import anytime as A
from ..models import transformer as T
from ..telemetry import TelemetryConfig, init_telemetry, record_anytime_step

_F32 = jnp.float32
_I32 = jnp.int32

__all__ = [
    "AnytimeConfig", "AnytimeKnobs", "AnytimeRequest", "AnytimeTables",
    "AnytimeCarry", "AnytimeResult", "AnytimeServeEngine",
]


# --------------------------------------------------------------------------- #
# Configuration, knobs, requests.
# --------------------------------------------------------------------------- #


@dataclass(frozen=True)
class AnytimeConfig:
    """Static engine configuration (hashable; baked into the jit trace).

    Latency model: a step costs ``t_base + unit_time * max(depth)``
    seconds; energy: ``e_base`` per non-idle step plus ``unit_energy``
    per unit of charged depth per slot, drawn from a capacitor of
    ``capacity`` joules refilled by the supply trace (``trace_dt``
    seconds per trace slot).  ``mandatory_units=0`` defers to the model
    config's ``resolved_mandatory_units``.
    """

    policy: str = "anytime"       # "anytime" | "edf" | "edf-m"
    batch_slots: int = 4          # continuous-batching slots (B)
    max_steps: int = 256          # scan horizon (T)
    prompt_len: int = 4           # prompt table width (P)
    max_new_tokens: int = 16      # per-request generation cap
    alpha: float = 0.1            # zeta laxity weight
    beta: float = 0.5             # zeta utility weight
    t_base: float = 0.02          # per-step fixed latency (s)
    unit_time: float = 0.05       # latency per unit of depth (s)
    e_base: float = 0.05          # energy per non-idle step (J)
    unit_energy: float = 0.1      # energy per unit of depth per slot (J)
    capacity: float = 50.0        # capacitor size (J)
    start_frac: float = 1.0       # initial charge fraction
    trace_dt: float = 1.0         # seconds per supply-trace slot
    mandatory_units: int = 0      # 0 => model config's mandatory prefix
    deadline_cap: bool = True     # anytime: laxity-budget depth cap
    window: Optional[int] = None  # attention window override

    def __post_init__(self):
        if self.policy not in ("anytime", "edf", "edf-m"):
            raise ValueError(f"unknown policy {self.policy!r}")


class AnytimeKnobs(NamedTuple):
    """Dynamic scheduler knobs (tunable without recompilation)."""

    exit_thr: jax.Array      # (U,) f32 per-unit margin thresholds
    use_exit_thr: jax.Array  # (U,) f32 0/1 per-unit enables
    eta: jax.Array           # () f32 harvest-predictability factor
    e_opt: jax.Array         # () f32 optional-work energy gate (J)


@dataclass(frozen=True)
class AnytimeRequest:
    """One serving request: prompt tokens, generation budget, timing."""

    prompt: Sequence[int]
    n_tokens: int
    release: float
    deadline: float


class AnytimeTables(NamedTuple):
    """Packed request tables (device arrays)."""

    prompt: jax.Array      # (N, P) i32
    prompt_len: jax.Array  # (N,) i32
    n_tokens: jax.Array    # (N,) i32
    release: jax.Array     # (N,) f32
    deadline: jax.Array    # (N,) f32


class AnytimeCarry(NamedTuple):
    """The scan carry: pure pytree, checkpointable at any segment
    boundary, shardable via :func:`repro.launch.sharding.state_specs`
    (the decode state's batch axis)."""

    now: jax.Array         # () f32 simulation clock
    energy: jax.Array      # () f32 capacitor charge
    state: Any             # stacked=False decode state for B slots
    slot_req: jax.Array    # (B,) i32 request index, -1 = free
    slot_next: jax.Array   # (B,) i32 next input token per slot
    req_status: jax.Array  # (N,) i32 0 wait / 1 run / 2 on-time / 3 late
    req_finish: jax.Array  # (N,) f32 completion time (0 until retired)
    req_agree: jax.Array   # (N,) i32 tokens agreeing with full depth
    req_tokens: jax.Array  # (N,) i32 tokens generated
    req_depth: jax.Array   # (N,) i32 summed depth over generated tokens
    tel: Any               # Telemetry, or None when disabled


# --------------------------------------------------------------------------- #
# Results.
# --------------------------------------------------------------------------- #


@dataclass(frozen=True)
class AnytimeResult:
    """Host-side per-request outcome + summary metrics.

    ``score`` is seeded-deterministic (pure function of the request set,
    knobs, and supply trace): the fraction of *requested* tokens that
    were generated by an on-time request AND agree with the full-depth
    prediction — timeliness and accuracy in one number, the quantity the
    regression gate tracks and ``adapt.tune`` maximises.
    """

    status: np.ndarray     # (N,) final req_status
    finish: np.ndarray     # (N,) completion time (horizon if unfinished)
    tardiness: np.ndarray  # (N,) max(0, finish - deadline)
    agree: np.ndarray      # (N,) tokens agreeing with full depth
    tokens: np.ndarray     # (N,) tokens generated
    depth_sum: np.ndarray  # (N,) summed depth over generated tokens
    requested: np.ndarray  # (N,) tokens requested
    horizon: float         # simulation end time
    n_units: int
    telemetry: Any = None

    @property
    def n_requests(self) -> int:
        return int(self.status.size)

    @property
    def completed(self) -> int:
        return int((self.status >= 2).sum())

    @property
    def on_time(self) -> int:
        return int((self.status == 2).sum())

    @property
    def missed(self) -> int:
        """Late completions + requests unfinished at the horizon."""
        return self.n_requests - self.on_time

    @property
    def mean_depth(self) -> float:
        return float(self.depth_sum.sum() / max(int(self.tokens.sum()), 1))

    @property
    def agreement(self) -> float:
        return float(self.agree.sum() / max(int(self.tokens.sum()), 1))

    @property
    def mean_tardiness(self) -> float:
        return float(self.tardiness.mean()) if self.tardiness.size else 0.0

    @property
    def score(self) -> float:
        good = np.where(self.status == 2, self.agree, 0)
        return float(good.sum() / max(int(self.requested.sum()), 1))

    def as_dict(self) -> dict:
        return {
            "n_requests": self.n_requests, "completed": self.completed,
            "on_time": self.on_time, "missed": self.missed,
            "mean_depth": self.mean_depth, "agreement": self.agreement,
            "mean_tardiness": self.mean_tardiness, "score": self.score,
            "horizon": self.horizon,
        }


# --------------------------------------------------------------------------- #
# The engine.
# --------------------------------------------------------------------------- #


def _bmask(mask: jax.Array, leaf: jax.Array) -> jax.Array:
    """Broadcast a (B,) mask over a batch-leading leaf."""
    return mask.reshape(mask.shape + (1,) * (leaf.ndim - 1))


class AnytimeServeEngine:
    """Continuous-batching anytime engine for one registered model config.

    ``supply`` is a :class:`repro.core.energy.Harvester` (its power trace
    is sampled with ``seed``), a precomputed watts array, or ``None`` for
    an always-ample persistent source.
    """

    def __init__(self, cfg, params, heads=None, *,
                 serve_cfg: AnytimeConfig = AnytimeConfig(),
                 supply=None, seed: int = 0):
        self.cfg = cfg
        self.params = params
        self.heads = heads if heads is not None else A.init_heads(cfg)
        self.scfg = serve_cfg
        self.n_units = cfg.n_units
        self.mandatory = (serve_cfg.mandatory_units
                          or cfg.resolved_mandatory_units)
        if not 1 <= self.mandatory <= self.n_units:
            raise ValueError(
                f"mandatory_units {self.mandatory} outside [1, "
                f"{self.n_units}]")
        sc = serve_cfg
        horizon = sc.max_steps * (sc.t_base + sc.unit_time * self.n_units)
        if supply is None:
            # persistent: always refill faster than the worst-case burn
            burn = (sc.e_base + sc.batch_slots * sc.unit_energy
                    * self.n_units) / max(sc.t_base, 1e-9)
            trace = np.full(1, burn, np.float64)
        elif isinstance(supply, EN.Harvester):
            n_slots = int(np.ceil(horizon / sc.trace_dt)) + 1
            trace = supply.power_trace(
                np.random.default_rng(seed), n_slots)
        else:
            trace = np.asarray(supply, np.float64)
        self.trace = jnp.asarray(trace, _F32)
        self._cache_len = sc.prompt_len + sc.max_new_tokens
        self._zero_state = T.init_decode_state(
            cfg, sc.batch_slots, self._cache_len, window=sc.window,
            cache_len=self._cache_len, stacked=False)
        self._seg_fns: dict = {}

    # ------------------------------------------------------------------ #
    def default_knobs(self, *, exit_thr=None, use_exit_thr=None,
                      eta: float = 1.0,
                      e_opt_fraction: float = 0.25) -> AnytimeKnobs:
        U = self.n_units
        if exit_thr is None:
            exit_thr = jnp.full((U,), self.cfg.utility_threshold, _F32)
        if use_exit_thr is None:
            use_exit_thr = jnp.ones((U,), _F32)
        return AnytimeKnobs(
            exit_thr=jnp.asarray(exit_thr, _F32).reshape(U),
            use_exit_thr=jnp.asarray(use_exit_thr, _F32).reshape(U),
            eta=jnp.asarray(eta, _F32),
            e_opt=jnp.asarray(e_opt_fraction * self.scfg.capacity, _F32),
        )

    def pack(self, requests: Sequence[AnytimeRequest]) -> AnytimeTables:
        """Pad/clip host requests into device tables."""
        sc = self.scfg
        N, P = len(requests), sc.prompt_len
        prompt = np.zeros((N, P), np.int32)
        plen = np.zeros((N,), np.int32)
        ntok = np.zeros((N,), np.int32)
        rel = np.zeros((N,), np.float32)
        ddl = np.zeros((N,), np.float32)
        for i, r in enumerate(requests):
            toks = np.asarray(list(r.prompt)[-P:], np.int32)
            if toks.size < 1:
                raise ValueError("empty prompt")
            prompt[i, :toks.size] = toks
            plen[i] = toks.size
            ntok[i] = min(max(int(r.n_tokens), 1), sc.max_new_tokens)
            rel[i] = r.release
            ddl[i] = r.deadline
        return AnytimeTables(
            prompt=jnp.asarray(prompt), prompt_len=jnp.asarray(plen),
            n_tokens=jnp.asarray(ntok), release=jnp.asarray(rel),
            deadline=jnp.asarray(ddl))

    def init_carry(self, tables: AnytimeTables, *,
                   telemetry: Optional[TelemetryConfig] = None
                   ) -> AnytimeCarry:
        N = tables.prompt.shape[0]
        B = self.scfg.batch_slots
        tel = (init_telemetry(telemetry, self.n_units)
               if telemetry is not None else None)
        carry = AnytimeCarry(
            now=jnp.zeros((), _F32),
            energy=jnp.asarray(
                self.scfg.start_frac * self.scfg.capacity, _F32),
            state=self._zero_state,
            slot_req=jnp.full((B,), -1, _I32),
            slot_next=jnp.zeros((B,), _I32),
            req_status=jnp.zeros((N,), _I32),
            req_finish=jnp.zeros((N,), _F32),
            req_agree=jnp.zeros((N,), _I32),
            req_tokens=jnp.zeros((N,), _I32),
            req_depth=jnp.zeros((N,), _I32),
            tel=tel,
        )
        # deep-copy every leaf: run() donates the carry into the segment
        # scan, which must neither invalidate the engine's cached zero
        # state nor see one deduplicated zeros constant at two argument
        # positions (XLA rejects donating the same buffer twice)
        return jax.tree.map(jnp.copy, carry)

    # ------------------------------------------------------------------ #
    def _step(self, weights, tables: AnytimeTables, carry: AnytimeCarry,
              knobs: AnytimeKnobs, tel_on: bool) -> AnytimeCarry:
        """One engine step; ``weights`` is ``(params, heads)``, an argument
        of the jitted segment rather than a constant folded into it."""
        cfg, sc = self.cfg, self.scfg
        B, U, m = sc.batch_slots, self.n_units, self.mandatory
        N = tables.prompt.shape[0]
        now, energy = carry.now, carry.energy
        slot_req, slot_next = carry.slot_req, carry.slot_next
        req_status = carry.req_status

        # --- admission: released, waiting requests into free slots ----- #
        laxity = tables.deadline - now
        if sc.policy == "anytime":
            scores = POL.zeta_intermittent_priority(
                laxity, 0.0, 1.0, sc.alpha, sc.beta, knobs.eta, energy,
                knobs.e_opt)
        else:
            scores = POL.edf_key(laxity, tables.release)
        waiting = (req_status == 0) & (tables.release <= now)
        scores = jnp.where(waiting, scores, POL.NEG)
        prev_slot_req = slot_req
        for b in range(B):
            best = jnp.argmax(scores).astype(_I32)
            ok = (slot_req[b] < 0) & (scores[best] > 0.5 * POL.NEG)
            slot_req = slot_req.at[b].set(
                jnp.where(ok, best, slot_req[b]))
            scores = jnp.where(ok, scores.at[best].set(POL.NEG), scores)
        admitted = slot_req != prev_slot_req                     # (B,)
        req = jnp.clip(slot_req, 0, N - 1)
        oob = jnp.where(admitted, req, N)
        req_status = req_status.at[oob].set(1, mode="drop")
        state = jax.tree.map(
            lambda a, z: jnp.where(_bmask(admitted, a), z, a),
            carry.state, self._zero_state)
        slot_next = jnp.where(admitted, tables.prompt[req, 0], slot_next)

        # --- power: brownout when the store can't cover the base cost -- #
        active = slot_req >= 0
        on = energy >= sc.e_base

        def run_model(st):
            params, heads = weights
            return A.unit_decode_step(cfg, params, heads, st, slot_next,
                                      window=sc.window)

        def skip_model(st):
            return (jnp.zeros((U, B, cfg.padded_vocab), _F32), st)

        unit_logits, new_state = jax.lax.cond(
            on, run_model, skip_model, state)
        run_mask = active & on

        # --- depth control --------------------------------------------- #
        plen = tables.prompt_len[req]
        ntok = tables.n_tokens[req]
        ddl = tables.deadline[req]
        pos = state["pos"]
        gen_step = pos >= plen - 1        # this step's output is generated
        if sc.policy == "edf":
            depth = jnp.full((B,), U, _I32)
        elif sc.policy == "edf-m":
            depth = jnp.full((B,), m, _I32)
        else:
            marg = A.margins(unit_logits)                       # (U, B)
            depth, _ = A.select_depth(marg, knobs.exit_thr,
                                      knobs.use_exit_thr, m)
            if sc.deadline_cap:
                # greedy per-token latency budget for the remaining work
                rem = jnp.maximum(ntok - jnp.maximum(pos - plen + 1, 0), 1)
                budget = (ddl - now) / rem
                d_cap = jnp.floor(
                    (budget - sc.t_base) / sc.unit_time).astype(_I32)
                depth = jnp.minimum(depth, d_cap)
            gate_open = knobs.eta * energy >= knobs.e_opt
            depth = jnp.where(gate_open, depth, m)
            depth = jnp.clip(depth, m, U)
        depth = jnp.where(gen_step, depth, U)   # prompt steps: full depth
        depth = jnp.where(run_mask, depth, 0)

        # --- continuous-batching cost ---------------------------------- #
        max_depth = jnp.max(depth)
        dt = sc.t_base + sc.unit_time * max_depth.astype(_F32)
        consume = (jnp.any(run_mask).astype(_F32) * sc.e_base
                   + sc.unit_energy * jnp.sum(depth).astype(_F32))
        slot_i = jnp.clip((now / sc.trace_dt).astype(_I32), 0,
                          self.trace.shape[0] - 1)
        new_energy = jnp.clip(energy - consume + self.trace[slot_i] * dt,
                              0.0, sc.capacity)
        new_now = now + dt

        # --- emission + retirement ------------------------------------- #
        emit_full = jnp.argmax(unit_logits[-1], -1).astype(_I32)
        picked = A.take_at_depth(unit_logits, jnp.maximum(depth, 1))
        emit = jnp.argmax(picked, -1).astype(_I32)
        next_pos = pos + 1
        nxt = jnp.where(
            next_pos < plen,
            tables.prompt[req, jnp.clip(next_pos, 0, sc.prompt_len - 1)],
            emit)
        slot_next = jnp.where(run_mask, nxt, slot_next)
        gen_now = run_mask & gen_step
        emitted_after = jnp.maximum(pos - plen + 2, 0)
        agree_now = gen_now & (emit == emit_full)
        gen_req = jnp.where(gen_now, req, N)
        req_agree = carry.req_agree.at[gen_req].add(
            agree_now.astype(_I32), mode="drop")
        req_tokens = carry.req_tokens.at[gen_req].add(1, mode="drop")
        req_depth = carry.req_depth.at[gen_req].add(depth, mode="drop")

        done = gen_now & (emitted_after >= ntok)
        ontime = done & (new_now <= ddl)
        done_req = jnp.where(done, req, N)
        req_status = req_status.at[done_req].set(
            jnp.where(ontime, 2, 3), mode="drop")
        req_finish = carry.req_finish.at[done_req].set(
            new_now, mode="drop")
        slot_req = jnp.where(done, -1, slot_req)

        tel = carry.tel
        if tel_on:
            bins = jnp.where(depth < U, depth - 1, U)
            depth_hist = jnp.sum(
                gen_now[:, None]
                & (bins[:, None] == jnp.arange(U + 1)[None, :]),
                axis=0).astype(_I32)
            slack = jnp.where(done, ddl - new_now, 0.0)
            tel = record_anytime_step(
                tel,
                releases=jnp.sum(admitted).astype(_I32),
                misses=jnp.sum(done & ~ontime).astype(_I32),
                scheduled=jnp.sum(ontime).astype(_I32),
                retired=jnp.sum(done).astype(_I32),
                slack_sum=jnp.sum(slack),
                slack_min=jnp.min(
                    jnp.where(done, ddl - new_now, jnp.inf)),
                depth_hist=depth_hist,
                occupancy=jnp.sum(active).astype(_I32),
                energy=new_energy, t=new_now)

        return AnytimeCarry(
            now=new_now, energy=new_energy, state=new_state,
            slot_req=slot_req, slot_next=slot_next,
            req_status=req_status, req_finish=req_finish,
            req_agree=req_agree, req_tokens=req_tokens,
            req_depth=req_depth, tel=tel)

    # ------------------------------------------------------------------ #
    def _segment_fn(self, n_steps: int, tel_on: bool):
        key = (n_steps, tel_on)
        if key not in self._seg_fns:
            def seg(carry, tables, knobs, weights):
                def body(c, _):
                    return self._step(weights, tables, c, knobs,
                                      tel_on), None
                carry, _ = jax.lax.scan(
                    body, carry, None, length=n_steps)
                return carry

            self._seg_fns[key] = jax.jit(seg, donate_argnums=(0,))
        return self._seg_fns[key]

    def run(self, requests, *, knobs: Optional[AnytimeKnobs] = None,
            telemetry: Optional[TelemetryConfig] = None,
            n_segments: int = 1, hook=None, mesh=None) -> AnytimeResult:
        """Serve ``requests`` (host :class:`AnytimeRequest` list or a
        packed :class:`AnytimeTables`) over ``max_steps`` scan steps.

        ``n_segments`` splits the horizon into checkpointable chunks —
        bit-exact for any segmentation; ``hook(seg_index, carry, knobs)``
        runs between segments and may return replacement
        :class:`AnytimeKnobs` (dynamic args: no recompilation).
        ``mesh`` shards the decode state's batch axis via
        :func:`repro.launch.sharding.state_specs`.
        """
        tables = (requests if isinstance(requests, AnytimeTables)
                  else self.pack(requests))
        knobs = knobs if knobs is not None else self.default_knobs()
        carry = self.init_carry(tables, telemetry=telemetry)
        if mesh is not None:
            carry = carry._replace(state=jax.device_put(
                carry.state,
                SH.named(mesh, SH.state_specs(mesh, carry.state))))
        T_total = self.scfg.max_steps
        if not 1 <= n_segments <= T_total:
            raise ValueError(f"n_segments {n_segments} outside "
                             f"[1, {T_total}]")
        base, extra = divmod(T_total, n_segments)
        tel_on = telemetry is not None
        for seg in range(n_segments):
            n_steps = base + (1 if seg < extra else 0)
            if n_steps == 0:
                continue
            carry = self._segment_fn(n_steps, tel_on)(
                carry, tables, knobs, (self.params, self.heads))
            if hook is not None:
                new = hook(seg, carry, knobs)
                if new is not None:
                    knobs = new
        return self._finalize(tables, carry)

    def _finalize(self, tables: AnytimeTables,
                  carry: AnytimeCarry) -> AnytimeResult:
        status = np.asarray(jax.device_get(carry.req_status))
        finish = np.asarray(jax.device_get(carry.req_finish), np.float64)
        deadline = np.asarray(jax.device_get(tables.deadline), np.float64)
        horizon = float(jax.device_get(carry.now))
        finish = np.where(status >= 2, finish, horizon)
        tardiness = np.maximum(0.0, finish - deadline)
        return AnytimeResult(
            status=status, finish=finish, tardiness=tardiness,
            agree=np.asarray(jax.device_get(carry.req_agree)),
            tokens=np.asarray(jax.device_get(carry.req_tokens)),
            depth_sum=np.asarray(jax.device_get(carry.req_depth)),
            requested=np.asarray(jax.device_get(tables.n_tokens)),
            horizon=horizon, n_units=self.n_units,
            telemetry=carry.tel)

    # ------------------------------------------------------------------ #
    def score_fn(self, tables: AnytimeTables, *,
                 tardiness_weight: float = 0.0):
        """A pure ``knobs -> scalar score`` function of the dynamic knobs
        (jit/vmap-able — the :mod:`repro.adapt` objective surface).

        Score = on-time agreed-token fraction, minus
        ``tardiness_weight`` x mean tardiness normalised by the mean
        deadline — the latency/energy-budget objective the exit
        thresholds are tuned against.
        """
        T_total = self.scfg.max_steps
        norm = jnp.maximum(jnp.mean(tables.deadline), 1e-6)

        def score(knobs: AnytimeKnobs):
            carry = self.init_carry(tables)

            def body(c, _):
                return self._step((self.params, self.heads), tables, c,
                                  knobs, False), None

            carry, _ = jax.lax.scan(body, carry, None, length=T_total)
            ontime = carry.req_status == 2
            good = jnp.sum(jnp.where(ontime, carry.req_agree, 0))
            frac = good / jnp.maximum(jnp.sum(tables.n_tokens), 1)
            finish = jnp.where(carry.req_status >= 2, carry.req_finish,
                               carry.now)
            tardy = jnp.mean(jnp.maximum(finish - tables.deadline, 0.0))
            return frac - tardiness_weight * tardy / norm

        return score
