"""Algorithm 1 — one FL round as a single jitted function.

Per round: sample uplink rates → per-device candidate H (policy) →
latency/energy estimates → PS utilities → top-K selection → masked
vmapped local SGD on the K selected clients (one loop to the cohort's
largest live H, with per-client iteration masks for the slots whose H
is smaller — static shapes instead of ragged loops) → FedAvg
(Pallas-kernel-backed weighted aggregation) → fleet-state update
(Algorithm 1 lines 18–27).

Method dispatch has two flavours sharing this one body:

  `make_round_body(model, cfg, method: MethodSpec, scenario)` — the
  selector/policy branches are Python `if`s resolved at trace time: one
  compiled program per method, bitwise-stable (the golden-history path).

  `make_round_body_mp(model, cfg, scenario)` — the method enters as a
  *traced* `methods.MethodParams` argument and the branches dispatch via
  `lax.switch` on its branch ids. Because the method is an argument
  pytree, the engine vmaps it: a whole (method × seed) campaign grid
  traces and compiles **once** (`engine.run_campaign_grid`). Under the
  method-axis vmap the switch lowers to compute-all-branches + select —
  the branches are cheap (S,) selector/policy math, while the expensive
  probe/training/aggregation work is shared outside the switch.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import async_agg
from repro.core import policy as pol
from repro.core import resilience as res
from repro.core import selection as sel
from repro.core import utility as util
from repro.core.async_agg import AsyncCfg
from repro.core.methods import (
    MethodParams,
    MethodSpec,
    selector_branches,
)
from repro.core.resilience import ResilienceCfg
from repro.core.state import AsyncState, FleetState
from repro.kernels.fedavg import ops as fedavg_ops
from repro.kernels.rewafl_select import ops as rsel_ops
from repro.models.fl_models import FLModel
from repro.sim import faults as flt
from repro.sim.devices import DeviceFleet
from repro.sim.dynamics.channel import effective_rate_mean
from repro.sim.dynamics.env import EnvState, step_env
from repro.sim.dynamics.scenarios import Scenario
from repro.sim.energy import min_round_cost, round_costs
from repro.sim.wireless import sample_rates, sample_rates_from_mean


@dataclasses.dataclass(frozen=True)
class FLConfig:
    n_select: int = 20
    alpha: float = 1.0          # latency-utility exponent (paper default 1)
    beta: float = 1.0           # energy-utility exponent (paper default 1)
    T_round: float = 60.0       # developer-preferred round duration (s)
    batch_size: int = 32
    probe_size: int = 32        # per-client samples for loss estimation
    lr: float = 0.05
    # uplink payload (bits). None -> the trained model's true size; the
    # benchmark scale trains a width-reduced proxy model but simulates the
    # paper-scale payload (~2 MB CNN / ~5 MB LSTM) so comm latency/energy
    # keep their real-testbed balance (DESIGN.md §Assumption-changes #1)
    uplink_bits: Optional[float] = None
    policy: pol.PolicyCfg = dataclasses.field(default_factory=pol.PolicyCfg)
    autofl_eta: float = 1.0
    autofl_ema: float = 0.5
    # probe the global model every N rounds instead of every round,
    # carrying the last probed per-device loss in FleetState.g_loss
    # between probes. 1 (default) probes every round — exact paper
    # semantics, bitwise-identical history. N > 1 amortises the (S·probe)
    # forward and staleness-lags Eqn (4)'s |Loss(θ_i)−Loss(θ)| signal,
    # the AutoFL reward, and the `global_loss` metric by < N rounds.
    probe_every: int = 1
    # resilience knobs (round deadline + robust update screen); the
    # default is fully inert — no extra traced ops, bitwise-unchanged
    # programs — and the screen auto-arms when the scenario injects
    # faults (core.resilience.ResilienceCfg)
    resilience: ResilienceCfg = dataclasses.field(
        default_factory=ResilienceCfg)
    # hot-path lowering (kernels/rewafl_select/ops.py): 'xla' is the
    # reference composition (golden histories are bitwise on it),
    # 'pallas' the fused utility→top-K→FedAvg pass, 'auto' resolves per
    # attached backend at trace time. On CPU both resolve to programs
    # with identical masks; 'pallas' additionally swaps the traced-ε
    # rank sort for the fused top-k emission.
    kernel_backend: str = "auto"


def _probe_losses(model: FLModel, params, cx, cy, probe: int) -> jax.Array:
    """(S,) mean loss and (S,) mean squared loss of the global model on a
    per-client probe subsample. cx: (S, n, ...), cy: (S, n).

    One flat (S·probe) forward instead of a vmap of S per-device
    forwards: the model sees a single batch axis (bitwise-identical
    per-sample losses — batching is outside every reduction — but a
    flat batched matmul/conv instead of S tiny ones)."""
    S = cx.shape[0]
    px, py = cx[:, :probe], cy[:, :probe]
    p = px.shape[1]  # the slice clamps when probe > samples-per-client
    flat_x = px.reshape((S * p,) + px.shape[2:])
    flat_y = py.reshape((S * p,) + py.shape[2:])
    ls = model.per_sample_loss(params, {"x": flat_x, "y": flat_y})
    ls = ls.reshape(S, p)
    return jnp.mean(ls, axis=1), jnp.mean(ls ** 2, axis=1)


def _local_sgd(model: FLModel, params, x, y, H, n_iters, key,
               cfg: FLConfig):
    """Masked local SGD: `n_iters` iterations (the cohort's largest H, a
    traced bound shared by every slot); iterations ≥ this slot's H are
    no-ops."""
    n = x.shape[0]
    grad_fn = jax.grad(model.loss)

    def body(it, p):
        k = jax.random.fold_in(key, it)
        idx = jax.random.randint(k, (cfg.batch_size,), 0, n)
        g = grad_fn(p, {"x": x[idx], "y": y[idx]})
        live = (it < H).astype(jnp.float32)
        return jax.tree.map(lambda pp, gg: pp - cfg.lr * live * gg, p, g)

    return jax.lax.fori_loop(0, n_iters, body, params)


def _fedavg(global_params, client_params, weights, backend=None):
    """θ' = θ + Σ w_k·(θ_k − θ)/Σw — via the fedavg kernel op. `backend`
    pins the aggregation lowering (FLConfig.kernel_backend, resolved);
    None keeps the op's legacy attached-backend heuristic."""
    wsum = jnp.maximum(jnp.sum(weights), 1e-9)
    wn = weights / wsum
    has = jnp.sum(weights) > 0

    def combine(g, c):
        agg = fedavg_ops.weighted_aggregate(c, wn,
                                            backend=backend)
        return jnp.where(has, agg.astype(g.dtype), g)

    return jax.tree.map(combine, global_params, client_params)


def sample_round_rates(key, fleet: DeviceFleet,
                       env: Optional[EnvState] = None) -> jax.Array:
    """One round's (S,) uplink rate draw — the single sampling point for
    every engine arm. Static scenarios (env=None) draw around the
    fleet's build-time mean; dynamic scenarios around the current
    channel state's effective mean. `sample_rates(key, fleet)` is
    exactly `sample_rates_from_mean(key, fleet.rate_mean, ...)`, so the
    static arm is bitwise-unchanged by the hoist
    (tests/test_async_engine.py::test_sample_round_rates_hoist)."""
    if env is not None:
        return sample_rates_from_mean(
            key, effective_rate_mean(env.channel_good, fleet),
            fleet.rate_sigma)
    return sample_rates(key, fleet)


def select_slots(selected: jax.Array, k: int):
    """(sel_idx, slot_live) for the K training slots of a selection mask.

    `jnp.nonzero(..., size=k, fill_value=0)` pads ascending indices with
    device index 0 when fewer than k devices are selected — without a
    slot mask, a participating device 0 would occupy every pad slot and
    be re-trained, re-weighted, and re-scattered once per pad.
    `slot_live` marks the real (non-pad) slots; every downstream per-slot
    quantity (participation, FedAvg weight, state scatter) must be gated
    on it so each device owns at most one live slot.
    """
    sel_idx = jnp.nonzero(selected, size=k, fill_value=0)[0]
    slot_live = jnp.arange(k) < jnp.sum(selected)
    return sel_idx, slot_live


def _build_round_body(model: FLModel, cfg: FLConfig,
                      method: Optional[MethodSpec],
                      scenario: Optional[Scenario],
                      acfg: Optional[AsyncCfg] = None):
    """Shared body factory. `method` is a static MethodSpec (Python
    branch dispatch, one compile per method) or None — in which case the
    returned function takes a traced `MethodParams` as leading argument
    and dispatches selector/policy via `lax.switch`.

    `acfg` switches the aggregation regime at trace time: None keeps the
    sync FedAvg barrier (bitwise-unchanged); an `AsyncCfg` splits the
    round into dispatch (push θ_k − θ into the pending buffer with a
    virtual-clock arrival time) and land (buffered staleness-weighted
    aggregation once M updates arrive) — the returned body then carries
    an `AsyncState` between `state` and `env`."""
    K = cfg.n_select
    model_bits = float(cfg.uplink_bits or model.param_bits)
    dyn = scenario is not None and scenario.dynamic
    # chaos/resilience trace-time gates: with every gate off, the body
    # below traces ZERO additional ops and draws from the same PRNG
    # stream — static-paper stays bitwise-golden (tests/test_dynamics).
    fcfg = scenario.faults if scenario is not None else flt.FaultCfg()
    faults_on = fcfg.enabled
    rcfg = cfg.resilience
    deadline_on = rcfg.deadline_s is not None
    screen_on = rcfg.screen_on(faults_on)
    chaos = faults_on or deadline_on      # delivery ≠ participation
    pcfg = cfg.policy
    # hot-path lowering, resolved once at trace time: every selection /
    # aggregation consumer below threads this through
    # kernels/rewafl_select (kb == "xla" reproduces the pre-kernel
    # graphs exactly — the golden-bitwise path)
    kb = rsel_ops.resolve_backend(cfg.kernel_backend)
    n_lands = acfg.lands(K) if acfg is not None else 0

    def round_fn(mp: Optional[MethodParams], params, state: FleetState,
                 astate: Optional[AsyncState], env: EnvState,
                 fleet: DeviceFleet, cx, cy, key, round_idx):
        S = fleet.n
        # jax.named_scope blocks below are HLO-metadata-only phase labels
        # (selection / local-update / aggregation / dynamics): they name
        # the ops in XLA profiler captures and Perfetto traces without
        # touching the computation — numerics stay bitwise-identical.
        if dyn:
            k_env, k_rate, k_sel, k_train = jax.random.split(key, 4)
            with jax.named_scope("round.dynamics"):
                env, state = step_env(scenario, fleet, env, state,
                                      round_idx, k_env, model_bits)
        else:
            k_rate, k_sel, k_train = jax.random.split(key, 3)
        rates = sample_round_rates(k_rate, fleet, env if dyn else None)

        # method hyperparameters: trace-time constants (MethodSpec) or
        # traced MethodParams leaves (the batched grid)
        if mp is None:
            alpha, beta = cfg.alpha, cfg.beta
            autofl_eta, autofl_ema = cfg.autofl_eta, cfg.autofl_ema
        else:
            alpha, beta = mp.alpha, mp.beta
            autofl_eta, autofl_ema = mp.autofl_eta, mp.autofl_ema

        # --- global-model probe (amortised when probe_every > 1) ---------
        with jax.named_scope("round.probe"):
            if cfg.probe_every > 1:
                g_loss = jax.lax.cond(
                    round_idx % cfg.probe_every == 0,
                    lambda: _probe_losses(model, params, cx, cy,
                                          cfg.probe_size)[0],
                    lambda: state.g_loss)
            else:
                g_loss, _ = _probe_losses(model, params, cx, cy,
                                          cfg.probe_size)

        # --- candidate H per policy (Algorithm 1 line 8) -----------------
        def h_fixed():
            return state.H  # stays at H0

        def h_adah():
            return pol.h_adah(round_idx, S, pcfg)

        def h_rewa():  # Eqn (3) growth gated by Eqn (4)
            eps = pol.stopping_eps(state.last_local_loss, g_loss,
                                   state.last_energy, fleet.e0_reserve,
                                   state.last_ecp)
            return pol.h_rewa(state.H, rates, eps, pcfg)

        if mp is None:
            H_cand = {"fixed": h_fixed, "adah": h_adah,
                      "rewa": h_rewa}[method.policy]()
        else:  # branch order = methods.POLICY_IDS
            H_cand = jax.lax.switch(mp.policy_id, (h_fixed, h_adah, h_rewa))

        # --- cost estimates (line 9) -------------------------------------
        costs = round_costs(fleet, H_cand, rates, model_bits)

        # --- utilities + selection (lines 13–16) -------------------------
        # churn gates selection exactly like dropout, but is transient
        with jax.named_scope("round.selection"):
            available = ((~state.dropped & env.online) if dyn
                         else ~state.dropped)
            stat = state.last_stat

            def sel_random():
                return sel.random_select(k_sel, K, available)

            def oort_utils():
                stat_tu = sel.temporal_uncertainty(stat, round_idx,
                                                   state.last_round)
                return util.oort_utility(stat_tu, costs.t_total,
                                         T_round=cfg.T_round, alpha=alpha)

            def rea_utils():
                return util.rewafl_utility(
                    stat, costs.t_total, costs.e_total,
                    state.residual_energy, fleet.e0_reserve,
                    T_round=cfg.T_round, alpha=alpha, beta=beta)

            def rea_inputs():
                return util.UtilityInputs(
                    stat, costs.t_total, costs.e_total,
                    state.residual_energy, fleet.e0_reserve)

            if mp is None:
                if method.selector == "random":
                    selected = sel_random()
                elif method.selector == "oort":
                    selected = rsel_ops.select_mask(
                        k_sel, K, available, method.exploration,
                        scores=oort_utils(), backend=kb)
                elif method.selector == "autofl":
                    selected = rsel_ops.select_mask(
                        k_sel, K, available, method.exploration,
                        scores=state.q_value, backend=kb)
                else:  # "rea": Eqn (2) — REAFL / REAFL+LUPA / REWAFL.
                    # ε=0 ≡ pure top-K ranking; the pallas backend fuses
                    # the utility math into the selection kernel from
                    # the raw FleetState/EnvState-derived leaves
                    selected = rsel_ops.select_mask(
                        k_sel, K, available, 0.0, ui=rea_inputs(),
                        T_round=cfg.T_round, alpha=alpha, beta=beta,
                        backend=kb)
            else:
                # one unified rank-space ε-greedy serves every selector:
                # the switch (branch order = methods.SELECTOR_IDS) only
                # picks the cheap score arithmetic, and mp.exploration is
                # the effective ε (random ≡ 1: all slots from the same
                # uniform draw random_select makes; rea ≡ 0: pure
                # ranking). One sort-based mechanism to compile instead
                # of four — masks stay bit-identical to the static
                # branches above.
                scores = jax.lax.switch(
                    mp.selector_id,
                    selector_branches({
                        "random": lambda: jnp.zeros_like(stat),  # ε=1
                        "oort": oort_utils,
                        "autofl": lambda: state.q_value,
                        "rea": rea_utils,
                    }))
                # kb == "pallas" swaps the (S,) stable-argsort rank for
                # the fused lax.top_k candidate emission — same masks
                # (shared tie rule), so compile-once grids keep their
                # bitwise parity with the static branches above
                selected = rsel_ops.select_traced(k_sel, scores, K,
                                                  available,
                                                  mp.exploration,
                                                  backend=kb)

        # --- feasibility: selected devices without enough battery fail ---
        feasible = costs.e_total < (state.residual_energy - fleet.e0_reserve)
        participating = selected & feasible
        failed = selected & ~feasible

        # --- fault injection (sim.faults; trace-gated side channel) ------
        # `t_round` is the realized per-device round time (straggler
        # spikes included); `delivered` is the subset of participants
        # whose update actually reaches the server. With all gates off
        # both alias the fault-free tensors — no new ops, same stream.
        t_round = costs.t_total
        if faults_on:
            fp = mp.faults if mp is not None else flt.fault_params(fcfg)
            dr = flt.fault_draws(key, S)
            with jax.named_scope("round.faults"):
                straggler = (participating
                             & (dr.u_straggler < fp.straggler_rate))
                t_round = jnp.where(straggler,
                                    costs.t_total * fp.straggler_mult,
                                    costs.t_total)
                # mid-round compute abort: h_frac of the local steps ran
                # (their energy still drains below); the update is lost
                aborted = participating & (dr.u_abort < fp.abort_rate)
                # upload loss: only a *bad* Gilbert–Elliott channel
                # loses updates — energy was spent transmitting. Inert
                # on static scenarios (channel_good ≡ True).
                lost = (participating & ~aborted & ~env.channel_good
                        & (dr.u_loss < fp.loss_rate))
                delivered = participating & ~aborted & ~lost
        else:
            delivered = participating
        if deadline_on:
            # round deadline: too-late survivors are cut from the
            # aggregation (FedAvg renormalizes over the rest) but their
            # round energy is already burned
            cut = delivered & (t_round > rcfg.deadline_s)
            delivered = delivered & ~cut

        # --- local training on the K selected slots ----------------------
        # pad slots (fewer than K selected) are dead: their (harmless)
        # training of device 0's data is discarded by the slot mask
        with jax.named_scope("round.local_update"):
            sel_idx, slot_live = select_slots(selected, K)
            part_k = participating[sel_idx] & slot_live
            Hk = H_cand[sel_idx]
            # the loop runs to the cohort's largest live H, not H_max:
            # every iteration past it would be a no-op in every slot
            n_iters = jnp.max(jnp.where(slot_live, Hk, 0))
            xk, yk = cx[sel_idx], cy[sel_idx]
            keys = jax.random.split(k_train, K)
            client_params = jax.vmap(
                lambda x, y, H, kk: _local_sgd(model, params, x, y, H,
                                               n_iters, kk, cfg)
            )(xk, yk, Hk, keys)
            deliver_k = (part_k if not chaos
                         else delivered[sel_idx] & slot_live)
            weights = (fleet.data_size[sel_idx].astype(jnp.float32)
                       * deliver_k.astype(jnp.float32))

        # --- update corruption + robust screen (core.resilience) ---------
        if faults_on:
            with jax.named_scope("round.faults"):
                corrupt = delivered & (dr.u_corrupt < fp.corrupt_rate)
                client_params = flt.corrupt_cohort(
                    client_params, params, corrupt[sel_idx] & deliver_k,
                    dr.u_cmode[sel_idx], scale=fcfg.corrupt_scale,
                    nan_frac=fcfg.corrupt_nan_frac)
        if screen_on:
            with jax.named_scope("round.screen"):
                client_params, weights, reject_k = res.screen_updates(
                    params, client_params, weights,
                    norm_mult=rcfg.norm_mult)
                rejected = jnp.zeros((S,), bool).at[
                    jnp.where(slot_live, sel_idx, S)].set(reject_k,
                                                          mode="drop")
            ok = delivered & ~rejected
            ok_k = deliver_k & ~reject_k
        else:
            ok = delivered
            ok_k = deliver_k
        if acfg is None:
            with jax.named_scope("round.aggregation"):
                new_params = _fedavg(params, client_params, weights, kb)
        else:
            # ---- async dispatch / land (core.async_agg) -----------------
            # Dispatch: the cohort snapshots θ now; its deltas enter the
            # pending buffer and arrive on the virtual clock after the
            # device's estimated round time (or a unit delay). Failed
            # devices still occupy a slot (weight 0) — the PS cannot
            # tell a crashed device from a slow one until it reports.
            with jax.named_scope("round.aggregation"):
                if acfg.delay == "unit":
                    delays = jnp.ones((K,), jnp.float32)
                else:  # "wall": compute + uplink time at the sampled
                    # rate (straggler-inflated when faults are on —
                    # t_round aliases t_total otherwise)
                    delays = t_round[sel_idx].astype(jnp.float32)
                if acfg.delay_jitter > 0.0:
                    k_delay = jax.random.fold_in(key, 0xA57C)
                    delays = delays * jnp.exp(
                        acfg.delay_jitter
                        * jax.random.normal(k_delay, (K,)))
                if mp is None:
                    m_eff = acfg.buffer_m
                else:  # 0 is the sync sentinel: aggregate full cohorts
                    m_eff = jnp.where(mp.buffer_m > 0, mp.buffer_m, K)
                pend_before = jnp.sum(astate.slot_live.astype(jnp.int32))
                # chaos drops non-delivered updates *before* dispatch —
                # a lost/aborted/cut upload never occupies a buffer
                # slot. The fault-free path keeps the legacy semantics
                # (failed devices hold weight-0 slots: the PS cannot
                # tell a crashed device from a slow one).
                push_live = slot_live if not (chaos or screen_on) else ok_k
                astate, n_pushed = async_agg.push_cohort(
                    astate, jax.tree.map(lambda c, p: c - p, client_params,
                                         params),
                    sel_idx, push_live, weights, delays)
                n_retried_r = jnp.zeros((), jnp.int32)
                n_expired_r = jnp.zeros((), jnp.int32)
                if acfg.ttl is not None:
                    astate, tinfo = async_agg.expire_and_retry(
                        astate, ttl=acfg.ttl,
                        max_retries=acfg.max_retries,
                        retry_backoff=acfg.retry_backoff)
                    n_retried_r = tinfo["n_retried"]
                    n_expired_r = tinfo["n_expired"]
                # strict-trigger liveness fix: when nothing new can be
                # dispatched (n_pushed == 0) a sub-M residue would park
                # in the buffer forever under `pending >= M`. Relax the
                # trigger to the live occupancy for this step's land
                # attempts so terminal partial cohorts still land.
                pend_after = jnp.sum(astate.slot_live.astype(jnp.int32))
                stuck = (n_pushed == 0) & (pend_after > 0)
                # under-K relaxation at the sync-like trigger (M = K):
                # an under-K cohort (availability < K) entering an EMPTY
                # buffer would otherwise park until the fleet recovers —
                # but M=K is exactly the regime sync FedAvg aggregates
                # every cohort immediately. Landing it keeps the virtual
                # clock moving and (server_lr=1) arms the bitwise sync
                # fast path: pend_before == 0 and n_landed == n_pushed.
                # Gated on m_eff == K so genuine buffering (M < K drains
                # sub-cohorts, M > K accumulates across rounds) is
                # untouched.
                fresh_under = ((pend_before == 0) & (n_pushed > 0)
                               & (n_pushed < m_eff) & (m_eff == K))
                m_land = jnp.where(
                    stuck | fresh_under,
                    jnp.maximum(jnp.minimum(m_eff, pend_after), 1), m_eff)
                # Land: fixed number of masked aggregation attempts,
                # enough to drain the dispatch back below M. The first
                # attempt arms the bitwise sync fast path: an aggregation
                # consuming exactly this cohort with zero staleness
                # returns the literal sync _fedavg graph on bit-identical
                # inputs.
                new_params = params
                n_agg = jnp.zeros((), jnp.int32)
                n_landed_r = jnp.zeros((), jnp.int32)
                stale_sum = jnp.zeros((), jnp.int32)
                for j in range(n_lands):
                    sync_agg = sync_pred = None
                    if j == 0 and acfg.server_lr == 1.0:
                        sync_agg = _fedavg(params, client_params,
                                           weights, kb)
                        sync_pred = (lambda n_landed:
                                     (pend_before == 0)
                                     & (n_landed == n_pushed))
                    new_params, astate, info = async_agg.land_once(
                        new_params, astate, m_land,
                        staleness_power=acfg.staleness_power,
                        server_lr=acfg.server_lr,
                        sync_aggregate=sync_agg, sync_pred=sync_pred,
                        backend=kb)
                    n_agg = n_agg + info["did_aggregate"]
                    n_landed_r = n_landed_r + info["n_landed"]
                    stale_sum = stale_sum + info["stale_sum"]

        # --- post-training local losses (stat-utility refresh) -----------
        def local_probe(p, x, y):
            ls = model.per_sample_loss(
                p, {"x": x[:cfg.probe_size], "y": y[:cfg.probe_size]})
            return jnp.mean(ls), jnp.mean(ls ** 2)

        l_loss_k, l_sq_k = jax.vmap(local_probe)(client_params, xk, yk)

        # --- state update (lines 18–27) ----------------------------------
        # `succ` gates the PS-state refresh: a device whose update never
        # reached (or never passed) the server keeps its stale PS view —
        # but its energy is gone regardless (aborts drain only the
        # fraction of compute that ran; comm never started). Fault-free
        # programs alias succ = participating: zero new ops.
        succ = participating if not (chaos or screen_on) else ok
        succ_k = part_k if not (chaos or screen_on) else ok_k
        e_spent = jnp.where(participating, costs.e_total, 0.0)
        if faults_on:
            e_spent = jnp.where(aborted, costs.e_comp * dr.h_frac, e_spent)
        new_E = state.residual_energy - e_spent
        new_u = jnp.where(succ, 0, state.u + 1)
        new_H = jnp.where(succ, H_cand, state.H)
        new_last_round = jnp.where(succ, round_idx, state.last_round)

        # dead pad slots scatter to an out-of-bounds index and are
        # dropped: a live slot for device 0 must not race a pad slot
        # writing device 0's stale value back
        scatter_idx = jnp.where(slot_live, sel_idx, S)

        def scatter(base, vals_k, mask_k):
            upd = base.at[scatter_idx].set(jnp.where(mask_k, vals_k,
                                                     base[sel_idx]),
                                           mode="drop")
            return upd

        stat_k = util.statistical_utility(fleet.data_size[sel_idx], l_sq_k)
        new_stat = scatter(state.last_stat, stat_k, succ_k)
        new_lll = scatter(state.last_local_loss, l_loss_k, succ_k)
        new_ecp = jnp.where(succ, costs.e_comp, state.last_ecp)
        new_lastE = jnp.where(succ, state.residual_energy,
                              state.last_energy)

        # AutoFL bandit value: EMA of (global-loss drop proxy)/energy
        loss_drop_k = jnp.maximum(g_loss[sel_idx] - l_loss_k, 0.0)
        reward_k = util.autofl_reward(loss_drop_k, costs.e_total[sel_idx],
                                      eta=autofl_eta)
        q_sel = (autofl_ema * state.q_value[sel_idx]
                 + (1 - autofl_ema) * reward_k * 1e3)
        new_q = scatter(state.q_value, q_sel, succ_k)

        # dropout: can no longer afford even H=1 + uplink at its mean
        # rate (paper: depleted devices disabled from participation).
        # Static scenarios: permanent, priced at the build-time mean.
        # Dynamic scenarios: recoverable — priced at the current
        # channel's effective mean (matching step_env's recovery rule),
        # and the next round's `step_env` clears it once charging refills
        # the battery past the threshold (unavailable_until_charged).
        min_cost = min_round_cost(
            fleet, model_bits,
            effective_rate_mean(env.channel_good, fleet) if dyn else None)
        new_dropped = state.dropped | failed | (
            new_E - fleet.e0_reserve <= min_cost)

        new_state = FleetState(
            residual_energy=new_E, H=new_H, u=new_u,
            last_round=new_last_round, last_stat=new_stat,
            last_local_loss=new_lll, last_ecp=new_ecp,
            last_energy=new_lastE, dropped=new_dropped, q_value=new_q,
            n_participations=state.n_participations
            + participating.astype(jnp.int32),
            n_selected=state.n_selected + selected.astype(jnp.int32),
            g_loss=g_loss,
        )
        n_part = jnp.sum(participating)
        # Raw metrics dict: per-round scalars plus the per-device (S,)
        # leaves (core.metrics.PER_DEVICE_METRICS). The engine decides
        # per telemetry mode what streams to the host as dense history
        # and what folds into on-device reducers — the round body just
        # reports everything it knows (unconsumed leaves are dropped at
        # trace time, so dense-mode programs stay bitwise-identical).
        # realized round latency: straggler-inflated, but never past the
        # deadline — the server stops waiting there (fault-free programs
        # alias t_round = costs.t_total: identical graph)
        latency = jnp.max(jnp.where(participating, t_round, 0.0))
        if deadline_on:
            latency = jnp.minimum(latency, rcfg.deadline_s)
        metrics = {
            "round_latency": latency,
            "round_energy": jnp.sum(e_spent),
            "n_participating": n_part,
            "n_failed": jnp.sum(failed),
            "n_dropped": jnp.sum(new_dropped),
            "mean_H_selected": jnp.sum(jnp.where(selected, H_cand, 0)
                                       ) / jnp.maximum(jnp.sum(selected), 1),
            "local_iters": n_iters,
            "global_loss": jnp.mean(g_loss),
            "n_available": jnp.sum(available),
            "n_charging": jnp.sum(env.charging),
            "n_online": jnp.sum(env.online),
            "selected": selected,
            "H": new_H,
            "residual_energy": new_E,
            "staleness": new_u,
        }
        # chaos counters (only traced when the matching gate is on, so
        # fault-free histories keep their exact schema)
        if faults_on:
            metrics.update({
                "n_aborted": jnp.sum(aborted.astype(jnp.int32)),
                "n_lost": jnp.sum(lost.astype(jnp.int32)),
                "n_corrupted": jnp.sum(corrupt.astype(jnp.int32)),
                "n_straggler": jnp.sum(straggler.astype(jnp.int32)),
            })
        if deadline_on:
            metrics["n_deadline_cut"] = jnp.sum(cut.astype(jnp.int32))
        if screen_on:
            metrics["n_rejected"] = jnp.sum(reject_k.astype(jnp.int32))
        if acfg is not None:
            metrics.update({
                # virtual wall clock + buffer health, streamed per round
                "wall_clock": astate.t_now,
                "server_version": astate.server_version,
                "n_pending": jnp.sum(astate.slot_live.astype(jnp.int32)),
                "n_aggregations": n_agg,
                "n_landed": n_landed_r,
                "mean_update_staleness": (
                    stale_sum.astype(jnp.float32)
                    / jnp.maximum(n_landed_r, 1).astype(jnp.float32)),
                # per-device (S,): staleness of the last landed update
                "update_staleness": astate.update_staleness,
            })
            if acfg.ttl is not None:
                metrics["n_retried"] = n_retried_r
                metrics["n_expired"] = n_expired_r
        return new_params, new_state, astate, env, metrics

    if acfg is not None:
        return round_fn

    def sync_fn(mp, params, state, env, fleet, cx, cy, key, round_idx):
        p, s, _, e, m = round_fn(mp, params, state, None, env, fleet,
                                 cx, cy, key, round_idx)
        return p, s, e, m

    return sync_fn


def make_round_body(model: FLModel, cfg: FLConfig, method: MethodSpec,
                    scenario: Optional[Scenario] = None):
    """Returns the *un-jitted*, closure-free
    round(params, state, env, fleet, cx, cy, key, round_idx)
    -> (params', state', env', metrics).

    The fleet (`sim.devices.DeviceFleet`) and stacked client data
    cx/cy ((S, n, ...)) are explicit pytree *arguments*, not trace-time
    constants — so the same traced body vmaps over per-seed fleets and
    partitions (engine.run_campaign_batch(per_seed_fleets=True)) and the
    engine shards them as argument pytrees. `bind_round_body` recovers
    the legacy round(params, state, env, key, round_idx) view by partial
    application; env: `sim.dynamics.EnvState`.

    `scenario` picks the fleet-dynamics regime (None ≡ static-paper):
    static scenarios skip every dynamics branch at trace time — identical
    PRNG stream and numerics to the pre-dynamics simulator, with env
    carried through untouched. Dynamic scenarios evolve env between
    rounds (channel migration, charging, churn) and gate selection on
    `env.online`.

    The raw body is what `launch.engine` scans over (`jax.lax.scan`
    re-traces it per chunk); `make_round_fn` is the one-round jitted view
    of the same computation, so engine and loop share numerics exactly.
    """
    body = _build_round_body(model, cfg, method, scenario)

    def round_fn(params, state: FleetState, env: EnvState,
                 fleet: DeviceFleet, cx, cy, key, round_idx):
        return body(None, params, state, env, fleet, cx, cy, key, round_idx)

    return round_fn


def make_async_round_body(model: FLModel, cfg: FLConfig, method: MethodSpec,
                          scenario: Optional[Scenario] = None,
                          async_cfg: AsyncCfg = AsyncCfg()):
    """Async (FedBuff-style) flavour of `make_round_body`:
    round(params, state, astate, env, fleet, cx, cy, key, round_idx)
    -> (params', state', astate', env', metrics), where `astate` is the
    pending-update buffer + virtual clock (`core.state.AsyncState`,
    build with `init_async_state(params, S, async_cfg.slots(K))`).
    Selection, training, and fleet-state updates are the *same traced
    graph* as the sync body; only the aggregation differs — dispatched
    deltas land after their wireless/compute delay and aggregate
    staleness-weighted once `async_cfg.buffer_m` have arrived."""
    body = _build_round_body(model, cfg, method, scenario, async_cfg)

    def round_fn(params, state: FleetState, astate: AsyncState,
                 env: EnvState, fleet: DeviceFleet, cx, cy, key, round_idx):
        return body(None, params, state, astate, env, fleet, cx, cy, key,
                    round_idx)

    return round_fn


def make_async_round_body_mp(model: FLModel, cfg: FLConfig,
                             scenario: Optional[Scenario] = None,
                             async_cfg: AsyncCfg = AsyncCfg()):
    """Traced-method async round:
    round(mp, params, state, astate, env, fleet, cx, cy, key, round_idx).
    `mp.buffer_m` sets each cell's aggregation trigger (0 = sync
    sentinel: aggregate full K-cohorts — with zero jitter such a cell
    reproduces the sync grid cell bitwise via the land fast path), so
    one compiled campaign grid covers sync × async methods. The static
    buffer capacity / land count come from `async_cfg`, which must cover
    the smallest buffer_m in the grid (`engine.run_campaign_grid`
    derives this automatically)."""
    return _build_round_body(model, cfg, None, scenario, async_cfg)


def make_round_body_mp(model: FLModel, cfg: FLConfig,
                       scenario: Optional[Scenario] = None):
    """The traced-method view of the round:
    round(mp, params, state, env, fleet, cx, cy, key, round_idx) with
    `mp: methods.MethodParams` a vmappable argument pytree — selector and
    policy dispatch via `lax.switch` on its branch ids, so one trace (and
    one XLA compile) covers every batchable method. Same PRNG stream,
    same ranking semantics, bit-identical selection masks to the static
    `make_round_body(model, cfg, spec, scenario)` at equal
    hyperparameters (`tests/test_engine.py` grid-parity tests)."""
    return _build_round_body(model, cfg, None, scenario)


def bind_round_body(body, fleet: DeviceFleet, cx, cy):
    """Partial-apply fleet/client data onto a closure-free round body,
    recovering the legacy round(params, state, env, key, round_idx)
    signature (same computation graph — trace-time constants instead of
    arguments, so numerics are unchanged)."""

    def round_fn(params, state: FleetState, env: EnvState, key, round_idx):
        return body(params, state, env, fleet, cx, cy, key, round_idx)

    return round_fn


def make_round_fn(model: FLModel, fleet: DeviceFleet, cx, cy,
                  cfg: FLConfig, method: MethodSpec,
                  scenario: Optional[Scenario] = None):
    """Returns jitted round(params, state, env, key, round_idx) ->
    (params', state', env', metrics). cx/cy: stacked client data
    (S, n, ...). The thin bound view of the closure-free
    `make_round_body` — today's API, same bitwise static-paper history."""
    return jax.jit(bind_round_body(make_round_body(model, cfg, method,
                                                   scenario),
                                   fleet, cx, cy))


def make_eval_fn(model: FLModel, test_x, test_y):
    @jax.jit
    def evaluate(params):
        return model.accuracy(params, {"x": test_x, "y": test_y})

    return evaluate
