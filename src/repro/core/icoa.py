"""ICOA — Iterative Covariance Optimization Algorithm (paper Sec 3.1).

One sweep (the paper's inner `for i = 1..D`):

    1. gradient of eta_tilde = 1^T A^{-1} 1 w.r.t. f_i, at the *current* F
    2. back-tracking search for the step size Delta
    3. f_hat_i = f_i + Delta * grad
    4. project onto H_i: retrain agent i's estimator with f_hat_i as outcome
    5. refresh agent i's row of F (and hence A) before moving to agent i+1

The outer loop runs sweeps until |eta_n - eta_{n-1}| < eps (or a sweep budget).
The sweep is fully jit-compiled: the agent loop is a `lax.fori_loop`, the
back-search a `lax.while_loop`, and the projection the agent family's `fit`.

Minimax Protection (Sec 4.2) changes two things, both handled here via
`alpha`/`delta`: the covariance feeding the gradient is assembled from an
N/alpha subsample (fresh each sweep — the paper re-transmits a new random
subsample every iteration), and the reported weights come from the robust
minimax solver instead of the closed form.

Three engines compute the same sweep (DESIGN.md §5/§10):

  * "incremental" (default): carries a core.covstate.CovState through the
    agent loop — closed-form gradient off the cached (A0+jitter)^{-1} 1,
    O(D^2) rank-2 SMW probes in the back-search, one fused row-Gram product
    per accept/commit.  O(N*D + D^2) per objective probe.
  * "fused": the incremental engine with its per-agent update chain fused
    into two passes over the residual matrix — the ENTIRE back-search
    collapses to a closed-form schedule (kernels.sweep.ref) off one cached
    matvec, and accept/commit folds into a single row-Gram + rank-2 SMW
    evaluation.  With cfg.use_kernel these two passes are the Pallas kernels
    of kernels.sweep.  Per agent update: O(N*D) twice + O(D^2), with NO
    O(N*D) work inside the back-search.  The incremental engine is its
    parity oracle (tests enforce 1e-10 relative f64 history parity).
  * "dense": the ground-truth oracle — rebuilds the D x D Gram and re-solves
    A^{-1} 1 from scratch at every probe, O(N*D^2 + D^3) each.  Retained
    because every incremental answer must match it (tests enforce 1e-5
    relative history parity).
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro import transport as transport_lib
from repro.analysis import sanitize
from repro.faults import inject as faults_inject
from repro.faults import trace as faults_trace
from repro.core import covariance as cov
from repro.core import covstate
from repro.core import ensemble
from repro.core import gradient
from repro.core import minimax
from repro.obs import taps as obs_taps
from repro.obs.spec import ObsSpec
from repro.transport import Ledger
from repro.transport import ledger as ledger_mod

__all__ = ["ICOAConfig", "ICOAState", "init_state", "sweep", "run", "run_scan",
           "converged_record", "ensemble_predict"]

# contractions over the N instances run at full f32 precision: at the
# default a TPU rounds f32 operands through bf16 passes, and the
# back-search's accept decisions amplify that (DESIGN.md §10.2)
_HIGHEST = jax.lax.Precision.HIGHEST


@dataclasses.dataclass(frozen=True)
class ICOAConfig:
    n_sweeps: int = 30
    eps: float = 1e-7          # outer-loop stopping tolerance on eta
    step0: float = 1.0         # initial back-search step (scaled by grad norm)
    backtrack: float = 0.5     # step shrink factor
    max_probes: int = 16       # back-search budget
    alpha: float = 1.0         # compression rate (1 = full residual exchange)
    delta: float = 0.0         # Minimax Protection box half-width (0 = off)
    minimax_steps: int = 300   # inner robust-weight solver budget
    minimax_lr: float = 0.05
    use_kernel: bool = False   # route Gram products through the Pallas kernel
    accept_reject: bool = True # beyond-paper: reject projections that worsen
                               # the objective (False = paper-faithful sweep,
                               # reproduces the Fig. 3 oscillation at delta=0)
    row_broadcast: bool = False  # beyond-paper collective schedule: gather
                               # residuals ONCE per sweep, then broadcast only
                               # the updated agent's row after each update —
                               # O(N*D) traffic/sweep instead of the paper's
                               # O(N*D^2), with identical math (§Perf C)
    engine: str = "incremental"  # "incremental" (rank-2 CovState updates) |
                               # "fused" (closed-form back-search + fused
                               # accept/commit, Pallas-kernel backed) |
                               # "dense" (recompute-from-scratch parity oracle)
    transport: Optional[transport_lib.Transport] = None  # resolved comm regime
                               # (topology + codec + byte budget); None = the
                               # legacy exact_f64/full/unbudgeted default.
                               # Frozen + hashable, so it rides this static
                               # jit argument (DESIGN.md §8)
    checks: str = "off"        # checkify sanitizer rail (DESIGN.md §9.2):
                               # "off" = bit-for-bit inert (zero extra traced
                               # ops); "raise" = named NaN/div-zero/OOB checks
                               # insert at trace time and failures raise.
                               # Part of this static cfg, so the jit cache
                               # keys sanitized and bare programs separately
    obs: Optional[ObsSpec] = None  # in-trace metric taps (DESIGN.md §13):
                               # None = off, zero extra traced ops (the
                               # FaultSpec static-gating discipline); a
                               # normalized ObsSpec selects named per-sweep
                               # taps collected inside the compiled sweep
                               # and returned as its 5th output


@dataclasses.dataclass
class ICOAState:
    params: Any                # stacked agent params, leading dim D
    f: jnp.ndarray             # (D, N) training predictions
    key: jax.Array


def _subsampled_a0(f: jnp.ndarray, y: jnp.ndarray, idx: Optional[jnp.ndarray],
                   cfg: ICOAConfig) -> jnp.ndarray:
    """A0 from the transmitted subsample (exact local diagonal, Sec 4.1)."""
    return cov.subsampled_gram(y[None, :] - f, idx, use_kernel=cfg.use_kernel)


def _eta_tilde_sub(f: jnp.ndarray, y: jnp.ndarray, idx: Optional[jnp.ndarray],
                   cfg: ICOAConfig) -> jnp.ndarray:
    """Objective from the covariance the agents can actually see.

    alpha == 1: exact A.  alpha > 1: off-diagonals from the idx subsample,
    exact local diagonal (paper Sec 4.1, delta_ii = 0).
    """
    return ensemble.eta_tilde(_subsampled_a0(f, y, idx, cfg))


def init_state(family, keys: jax.Array, xcols: jnp.ndarray, y: jnp.ndarray) -> ICOAState:
    """Non-cooperative warm start: every agent fits y directly (averaging init)."""
    fit0 = jax.vmap(lambda k, x: family.fit(family.init(k), x, y))
    params = fit0(keys, xcols)
    f = jax.vmap(family.predict)(params, xcols)
    return ICOAState(params=params, f=f, key=keys[0])


@partial(jax.jit, static_argnames=("family", "cfg"))
def sweep(family, cfg: ICOAConfig, params: Any, f: jnp.ndarray,
          xcols: jnp.ndarray, y: jnp.ndarray, key: jax.Array,
          ledger: Optional[Ledger] = None, round_=None):
    """One full round-robin sweep over all D agents (jit-compiled).

    Unprotected (delta == 0): maximise eta_tilde = 1^T A^{-1} 1 (paper Sec 3.1).

    Minimax-protected (delta > 0): each agent first solves the robust inner
    problem for a* on the subsampled A0, then takes a descent step on the
    Danskin surrogate  a*^T A0(f) a*  with a* held fixed. Because
    zeta(f') <= g(a*, f') < g(a*, f) = zeta(f), an improvement in the
    surrogate is an improvement in the true worst-case objective — this is the
    numerically-stable realisation of the paper's "perturb (25)" remark.

    cfg.engine picks the covariance engine: "incremental" carries a rank-2
    updated CovState, "dense" recomputes every probe from scratch (oracle).

    `cfg.transport` picks the communication regime (DESIGN.md §8): every
    transmitted residual payload passes the codec (relayed `ecc` hops on
    sparse topologies) before entering the shared covariance state, and the
    traced `ledger` is charged from measured payload sizes — pass the ledger
    returned by the previous sweep to keep a running byte total (a byte
    budget gates row broadcasts against it).  Returns
    (params, f, key, ledger, taps) — `taps` is the per-sweep tap dict of
    `cfg.obs` ({} when obs is off: the dict is a valid empty pytree and the
    program is bit-identical to the tap-free one).

    `cfg.checks` switches the checkify sanitizer rail (DESIGN.md §9.2): the
    scope below holds the trace-time flag open while THIS program traces, so
    the check sites in covstate/transport insert iff the static cfg says so —
    callers with checks="raise" must run under `analysis.checked` (icoa.run
    and api.batch_fit do this) to functionalize them.

    `round_` (optional traced int32) is the global sweep index — the fault
    layer's event coordinate: with `cfg.transport.faults` set, every drop /
    corruption / straggle / crash event is a pure function of
    (FaultSpec.seed, round_, agent), so runs replay bit-identically
    (repro.faults).  Without faults the round is ignored.
    """
    with sanitize.sanitize_scope(cfg.checks):
        params, f, key, ledger, taps = _sweep_impl(family, cfg, params, f,
                                                   xcols, y, key, ledger,
                                                   round_)
        f = sanitize.check_finite(f, "icoa.sweep: prediction matrix f")
    return params, f, key, ledger, taps


def _sweep_impl(family, cfg: ICOAConfig, params: Any, f: jnp.ndarray,
                xcols: jnp.ndarray, y: jnp.ndarray, key: jax.Array,
                ledger: Optional[Ledger], round_=None):
    d, n = f.shape
    tp = (cfg.transport or transport_lib.default_transport(d)).validate_for(d)
    transport_lib.require_budget_engine(tp, cfg.engine)
    faults_inject.require_fault_engine(tp, cfg)
    if ledger is None:
        ledger = Ledger.empty()
    m = cov.subsample_size(n, cfg.alpha) if cfg.alpha > 1.0 else n
    ledger_mod.ensure_sweep_capacity(
        tp, cfg.n_sweeps, m, split=cfg.alpha > 1.0,
        row_wise=cfg.engine in ("incremental", "fused") or cfg.row_broadcast,
        ledger=ledger,
        retries=0 if tp.faults is None else tp.faults.max_retries)
    rnd = jnp.asarray(0 if round_ is None else round_, jnp.int32)
    idx = None
    if cfg.alpha > 1.0:
        key, sub = jax.random.split(key)
        idx = cov.subsample_indices(sub, n, cfg.alpha)

    if cfg.engine == "incremental":
        params, f, ledger, taps = _sweep_incremental(
            family, cfg, tp, params, f, xcols, y, idx, ledger, rnd)
    elif cfg.engine == "fused":
        params, f, ledger, taps = _sweep_fused(
            family, cfg, tp, params, f, xcols, y, idx, ledger, rnd)
    else:
        params, f, ledger, taps = _sweep_dense(
            family, cfg, tp, params, f, xcols, y, idx, ledger)
    return params, f, key, ledger, taps


def _transported_a0(tp, cfg: ICOAConfig, f: jnp.ndarray, y: jnp.ndarray,
                    idx: Optional[jnp.ndarray]) -> jnp.ndarray:
    """A0 as the agents RECEIVE it: every transmitted row (and, under the
    Sec 4.1 split, every exact-diagonal scalar) passes the codec relay with
    straight-through gradients, so the dense objective — and its autodiff
    gradient — sees the lossy payloads.  Identity transports short-circuit
    to exactly `covariance.subsampled_gram`'s operations (bit-for-bit parity
    with the pre-transport solver)."""
    r = y[None, :] - f
    if idx is None:
        return cov.gram(tp.relay_rows_st(r), use_kernel=cfg.use_kernel)
    exact_diag = tp.relay_scalars_st(jnp.sum(r * r, axis=1) / r.shape[1])
    return cov.spliced_gram(tp.relay_rows_st(r[:, idx]), exact_diag,
                            use_kernel=cfg.use_kernel)


def _sweep_dense(family, cfg: ICOAConfig, tp, params: Any, f: jnp.ndarray,
                 xcols: jnp.ndarray, y: jnp.ndarray, idx: Optional[jnp.ndarray],
                 ledger: Ledger):
    """Recompute-from-scratch engine: every objective probe pays the full
    O(N*D^2) Gram + O(D^3) solve.  The parity oracle for the engine below.

    Transport semantics: the paper-faithful schedule re-transmits every row
    before every update, so every objective evaluation sees freshly-coded
    payloads (`_transported_a0`); the ledger charges D re-gathers per sweep
    (one per agent update), or the row-wise 2-gather price under
    cfg.row_broadcast — matching the analytic table exactly for exact codecs
    on the full topology (DESIGN.md §8)."""
    d, n = f.shape
    m = n if idx is None else idx.shape[0]
    ledger = ledger.charge(ledger_mod.icoa_sweep_cost(
        tp, m, split=idx is not None, row_wise=cfg.row_broadcast))
    taps0 = obs_taps.init_engine_taps(cfg.obs, d, f.dtype)
    if "codec_error" in taps0:
        # the dense schedule re-codes every probe; the tap reports the
        # sweep-start round trip (the same payload the other engines gather)
        r0 = y[None, :] - f
        sent0 = r0 if idx is None else r0[:, idx]
        taps0 = obs_taps.tap_codec_error(taps0, cfg.obs, sent0,
                                         tp.relay_rows(sent0))

    if cfg.delta > 0.0:
        def obj(ff):
            a0 = _transported_a0(tp, cfg, ff, y, idx)
            a = jax.lax.stop_gradient(
                minimax.robust_weights(a0, cfg.delta, steps=cfg.minimax_steps, lr=cfg.minimax_lr))
            # surrogate: worst-case quadratic at the fixed robust weights
            return -(minimax.robust_objective(a, a0, cfg.delta))  # maximise -zeta
    else:
        def obj(ff):
            return ensemble.eta_tilde(_transported_a0(tp, cfg, ff, y, idx))

    def update_agent(i, carry):
        params, f, tps = carry
        g = jax.grad(lambda fi: obj(f.at[i].set(fi)))(f[i])
        gnorm = jnp.linalg.norm(g) + 1e-30
        g_unit = g / gnorm
        eta0 = obj(f)

        # back-search: shrink until the objective strictly improves
        def cond(state):
            step, probes = state
            improved = obj(f.at[i].set(f[i] + step * g_unit)) > eta0
            return jnp.logical_and(~improved, probes < cfg.max_probes)

        def body(state):
            step, probes = state
            return step * cfg.backtrack, probes + 1

        step0 = cfg.step0 * jnp.sqrt(jnp.asarray(n, f.dtype))  # scale-free start
        step, probes = jax.lax.while_loop(cond, body,
                                          (step0, jnp.asarray(0, jnp.int32)))
        # if the budget ran out without improvement, take no step
        step = jnp.where(probes >= cfg.max_probes, 0.0, step)

        f_hat = f[i] + step * g_unit
        # projection onto H_i: retrain with f_hat as the outcome
        p_old = jax.tree.map(lambda t: t[i], params)
        p_new = family.fit(p_old, xcols[i], f_hat)
        f_new = family.predict(p_new, xcols[i])
        # accept/reject AFTER projection: the projection is not a descent
        # step, so without this guard eta drifts upward at the plateau
        # (beyond-paper fix; the paper's convergence claim is empirical)
        accept = (obj(f.at[i].set(f_new)) > eta0) if cfg.accept_reject else jnp.bool_(True)
        tps = obs_taps.tap_accept(tps, cfg.obs, i, accept)
        p_i = jax.tree.map(lambda new, old: jnp.where(accept, new, old), p_new, p_old)
        f_i = jnp.where(accept, f_new, f[i])
        params = jax.tree.map(lambda t, u: t.at[i].set(u), params, p_i)
        return params, f.at[i].set(f_i), tps

    params, f, taps = jax.lax.fori_loop(0, d, update_agent, (params, f, taps0))
    return params, f, ledger, taps


def _sweep_incremental(family, cfg: ICOAConfig, tp, params: Any, f: jnp.ndarray,
                       xcols: jnp.ndarray, y: jnp.ndarray,
                       idx: Optional[jnp.ndarray], ledger: Ledger, rnd=None):
    """Rank-2 CovState engine: O(N*D + D^2) per objective probe.

    The CovState is rebuilt from f at sweep start — that full solve IS the
    once-per-sweep refresh bounding SMW drift; every in-sweep probe/commit is
    a rank-2 update.  Math is identical to `_sweep_dense` (same gradient, via
    the closed form of core.gradient applied to the cached inverse action;
    same back-search; same accept/reject), so histories agree to fp accuracy.

    Transport semantics: the engine's transmissions are exactly the gather at
    sweep start and one candidate-row broadcast per agent update — each
    passes the codec relay before entering the carried CovState (probes are
    local SMW algebra: no traffic, no coding).  The ledger charges the
    measured payload bytes; under a byte budget the per-agent broadcast is
    gated (an unaffordable broadcast skips the agent's commit — nobody
    received the row) and `greedy_eta` reorders the round-robin by the
    cached-probe priority (transport.policy.greedy_order).

    Fault semantics (tp.faults set; repro.faults, DESIGN.md §12): the gather
    charges only the alive agents' floods, each candidate broadcast rolls
    the seeded drop/straggle trace — undelivered or skipped rows forfeit
    the commit exactly like an unaffordable one, with retransmit attempts
    charged to the ledger — and delivered rows may arrive bit-flipped
    (faults.trace.corrupt) before they touch the shared CovState.
    """
    d, n = f.shape
    m = n if idx is None else idx.shape[0]
    uk = cfg.use_kernel
    protected = cfg.delta > 0.0
    split = idx is not None
    budget = tp.byte_budget
    fl = tp.faults

    r0 = y[None, :] - f
    sent = r0 if idx is None else r0[:, idx]
    rel = tp.relay_rows(sent)
    if idx is None:
        cs0 = covstate.build(rel, use_kernel=uk)
    else:
        cs0 = covstate.build(rel,
                             exact_diag=tp.relay_scalars(jnp.sum(r0 * r0, axis=1) / n),
                             use_kernel=uk)
    taps0 = obs_taps.init_engine_taps(cfg.obs, d, f.dtype)
    taps0 = obs_taps.tap_codec_error(taps0, cfg.obs, sent, rel)

    # the local engine's back-search starts at step0*sqrt(n), so the greedy
    # priority probes at that scale too (transport.policy.budget_setup)
    if fl is not None:
        alive = faults_trace.alive_at(fl, d, rnd)
        live, order, bcosts, ledger = faults_inject.budget_setup(
            tp, cs0, ledger, m, split,
            step0=cfg.step0 * jnp.sqrt(jnp.asarray(n, f.dtype)), alive=alive)
    else:
        alive = None
        live, order, bcosts, ledger = transport_lib.budget_setup(
            tp, cs0, ledger, m, split,
            step0=cfg.step0 * jnp.sqrt(jnp.asarray(n, f.dtype)))

    def robust_probe(cs, i, u):
        return covstate.robust_eta_probe(cs, i, u, cfg.delta,
                                         cfg.minimax_steps, cfg.minimax_lr)

    def update_agent(slot, carry):
        params, f, cs, led, tps = carry
        i = slot if order is None else order[slot]
        r_i = y - f[i]

        if protected:
            v = minimax.robust_weights(cs.a0, cfg.delta, steps=cfg.minimax_steps,
                                       lr=cfg.minimax_lr,
                                       a_init=cs.s / jnp.sum(cs.s))
            eta0 = -minimax.robust_objective(v, cs.a0, cfg.delta)
        else:
            v = cs.s
            eta0 = cs.eta_tilde

        # closed-form gradient off the cached solve state (core.gradient)
        if idx is None:
            g = gradient.cached_row_gradient(v, cs.r_sub, i)
        else:
            # Sec 4.1 split: subsampled off-diagonals + exact local diagonal
            g = (2.0 / n) * (v[i] * v[i]) * r_i
            g = g.at[idx].add(
                gradient.cached_row_gradient(v, cs.r_sub, i, exclude_self=True))
        gnorm = jnp.linalg.norm(g) + 1e-30
        g_unit = g / gnorm

        # back-search: one row-Gram product, then O(D^2) SMW probes.  The
        # probe direction is fixed, so u(step) assembles from precomputed
        # pieces — the residual delta of probing step is -step * g_unit.
        g_sub = g_unit if idx is None else g_unit[idx]
        p = covstate.row_product(g_sub, cs.r_sub, use_kernel=uk) / m
        gg = jnp.vdot(g_sub, g_sub, precision=_HIGHEST)
        c1 = jnp.vdot(r_i, g_unit, precision=_HIGHEST)  # exact-diag cross term

        def u_of(step):
            w = -step * p
            if idx is None:
                return w.at[i].add(step * step * gg / (2.0 * m))
            ddiag = (step * step - 2.0 * step * c1) / n   # ||g_unit|| = 1
            return w.at[i].set(0.5 * ddiag)

        def probe_obj(step):
            u = u_of(step)
            if protected:
                return robust_probe(cs, i, u)
            return covstate.eta_probe(cs, i, u)

        def cond(state):
            step, probes = state
            improved = probe_obj(step) > eta0
            return jnp.logical_and(~improved, probes < cfg.max_probes)

        def body(state):
            step, probes = state
            return step * cfg.backtrack, probes + 1

        step0 = cfg.step0 * jnp.sqrt(jnp.asarray(n, f.dtype))  # scale-free start
        step, probes = jax.lax.while_loop(cond, body,
                                          (step0, jnp.asarray(0, jnp.int32)))
        step = jnp.where(probes >= cfg.max_probes, 0.0, step)

        f_hat = f[i] + step * g_unit
        p_old = jax.tree.map(lambda t: t[i], params)
        p_new = family.fit(p_old, xcols[i], f_hat)
        f_new = family.predict(p_new, xcols[i])

        # accept/reject AND commit share one rank-2 row update (the projected
        # row is an arbitrary delta, so this is the second row-Gram product).
        # The candidate row is what actually crosses the wire: it passes the
        # codec relay before touching the shared state (identity for exact
        # codecs), and under a byte budget its broadcast must be affordable.
        r_new = y - f_new
        r_new_sub = tp.relay_row(r_new if idx is None else r_new[idx], i)
        if fl is not None:
            # corruption strikes the delivered wire view only: the agent's
            # own params/f stay clean (it knows what it sent) — the shared
            # covariance state is what absorbs the flipped payload
            r_new_sub = faults_trace.corrupt(fl, r_new_sub, rnd, i)
        if idx is None:
            ddiag_acc = None
        else:
            ddiag_acc = tp.relay_scalar(
                jnp.vdot(r_new, r_new, precision=_HIGHEST) / n, i) - cs.a0[i, i]
        u_acc = covstate.row_update_vector(cs, i, r_new_sub - cs.r_sub[i],
                                           ddiag=ddiag_acc, use_kernel=uk)
        if cfg.accept_reject:
            obj_post = (robust_probe(cs, i, u_acc) if protected
                        else covstate.eta_probe(cs, i, u_acc))
            accept = obj_post > eta0
        else:
            accept = jnp.bool_(True)

        if fl is not None:
            ok, led = faults_inject.gate_broadcast(fl, led, live, bcosts, i,
                                                   alive[i], rnd, budget)
            accept = jnp.logical_and(accept, ok)
            tps = obs_taps.tap_fault_retries(tps, cfg.obs, fl, rnd, i, alive[i])
        elif budget is not None:
            can_tx, led = transport_lib.gate_broadcast(led, live, bcosts, i,
                                                       budget)
            accept = jnp.logical_and(accept, can_tx)
            tps = obs_taps.tap_budget_reject(tps, cfg.obs, can_tx)
        tps = obs_taps.tap_accept(tps, cfg.obs, i, accept)

        p_i = jax.tree.map(lambda new, old: jnp.where(accept, new, old), p_new, p_old)
        f_i = jnp.where(accept, f_new, f[i])
        params = jax.tree.map(lambda t, u_: t.at[i].set(u_), params, p_i)
        f = f.at[i].set(f_i)

        cs_next = covstate.apply_row_update(cs, i, r_new_sub, u_acc)
        cs = jax.tree.map(lambda a, b: jnp.where(accept, a, b), cs_next, cs)
        return params, f, cs, led, tps

    params, f, _, ledger, taps = jax.lax.fori_loop(
        0, d, update_agent, (params, f, cs0, ledger, taps0))
    return params, f, ledger, taps


def _small_inv(gm: jnp.ndarray) -> jnp.ndarray:
    """Closed-form batched inverse for trailing (P, P), P static and tiny.

    The fused engine's projector precompute inverts D feature Grams of the
    agent family's (static) feature count P; for the paper's families P <= 5,
    and for P <= 2 the cofactor form beats the batched LAPACK dispatch by
    ~8x on CPU without a dtype change."""
    p = gm.shape[-1]
    if p == 1:
        return 1.0 / gm
    if p == 2:
        a, b = gm[..., 0, 0], gm[..., 0, 1]
        c, d = gm[..., 1, 0], gm[..., 1, 1]
        det = a * d - b * c
        return jnp.stack([jnp.stack([d, -b], -1),
                          jnp.stack([-c, a], -1)], -2) / det[..., None, None]
    return jnp.linalg.inv(gm)


def _poly_projector(xcols: jnp.ndarray, degree: int, ridge: float):
    """Per-agent ridge projector for PolynomialFamily, precomputed once per
    sweep: phiT (D, P, N) transposed features (row-major contiguous for the
    in-loop matvecs) and Ginv (D, P, P) = (phi^T phi + ridge I)^{-1}.

    The P x P Gram is assembled by a static python loop over contiguous phiT
    rows — for tiny static P this lowers to P^2 fused row products, an order
    of magnitude cheaper on CPU than the batched einsum path."""
    from repro.agents.polynomial import _features  # agents -> jax only: no cycle

    phi_t = jax.vmap(lambda x: _features(x, degree).T)(xcols)
    p = phi_t.shape[1]
    rows = []
    for a in range(p):
        rows.append(jnp.stack([jnp.sum(phi_t[:, a, :] * phi_t[:, b, :], axis=-1)
                               for b in range(p)], -1))
    gm = jnp.stack(rows, -2) + ridge * jnp.eye(p, dtype=phi_t.dtype)
    return phi_t, _small_inv(gm)


def _sweep_fused(family, cfg: ICOAConfig, tp, params: Any, f: jnp.ndarray,
                 xcols: jnp.ndarray, y: jnp.ndarray,
                 idx: Optional[jnp.ndarray], ledger: Ledger, rnd=None):
    """Fused engine: the incremental sweep with every per-agent O(N*D) pass
    either eliminated or fused (kernels.sweep; DESIGN.md §10).

    Three fusions relative to `_sweep_incremental`, same math throughout:

      * closed-form back-search — the probe direction is fixed, so the whole
        step schedule is evaluated at once from one cached matvec
        (kernels.sweep.ref.probe_etas_closed) instead of an O(D^2) SMW probe
        per while_loop iteration;
      * algebraic probe product — for alpha = 1 the row product R @ g_unit
        equals (2 s_i / (m gnorm)) * (A0 @ s) on the CARRIED Gram, deleting
        the probe-side O(N*D) pass entirely (the Sec 4.1 split keeps the
        pass: its spliced diagonal breaks the identity);
      * fused accept/commit — row-Gram, post-projection objective probe,
        accept/reject and the rank-2 SMW update evaluate as one operation
        (kernels.sweep commit) with accept folded into the coefficients, so
        rejection is an exact no-op instead of a whole-state double-buffer.

    `cfg.use_kernel` routes the two remaining O(N*D) passes through the
    Pallas kernels: the alpha=1 probe pass (cross/p/||g|| in ONE pass over
    the VMEM-resident residual tile) and the commit pass.  PolynomialFamily
    projections use a once-per-sweep precomputed (phiT, Ginv) projector;
    other families fall back to family.fit inside the loop.

    Transport/ledger semantics are the incremental engine's, call for call:
    gather + budget_setup at sweep start, one gated candidate-row broadcast
    per agent update.  Minimax protection (cfg.delta > 0) delegates to the
    incremental engine — its robust inner solve iterates on the full A0 and
    has no closed-form schedule.
    """
    from repro.kernels.sweep import ops as sweep_ops
    from repro.kernels.sweep import ref as sweep_ref

    if cfg.delta > 0.0:
        return _sweep_incremental(family, cfg, tp, params, f, xcols, y, idx,
                                  ledger, rnd)

    d, n = f.shape
    m = n if idx is None else idx.shape[0]
    uk = cfg.use_kernel
    budget = tp.byte_budget
    fl = tp.faults

    r0 = y[None, :] - f
    sent = r0 if idx is None else r0[:, idx]
    rel = tp.relay_rows(sent)
    if idx is None:
        cs0 = covstate.build(rel, use_kernel=uk)
    else:
        cs0 = covstate.build(rel,
                             exact_diag=tp.relay_scalars(jnp.sum(r0 * r0, axis=1) / n),
                             use_kernel=uk)
    taps0 = obs_taps.init_engine_taps(cfg.obs, d, f.dtype)
    taps0 = obs_taps.tap_codec_error(taps0, cfg.obs, sent, rel)

    step0 = cfg.step0 * jnp.sqrt(jnp.asarray(n, f.dtype))
    if fl is not None:
        alive = faults_trace.alive_at(fl, d, rnd)
        live, order, bcosts, ledger = faults_inject.budget_setup(
            tp, cs0, ledger, m, idx is not None, step0=step0, alive=alive)
    else:
        alive = None
        live, order, bcosts, ledger = transport_lib.budget_setup(
            tp, cs0, ledger, m, idx is not None, step0=step0)

    # steps[k] = step0 * backtrack^k via cumprod — the same left-associated
    # multiply chain the incremental while_loop performs, so knife-edge step
    # selections cannot drift on association order
    steps = jnp.cumprod(jnp.concatenate(
        [step0[None], jnp.full((cfg.max_probes - 1,), cfg.backtrack, f.dtype)]))
    neg_inf = jnp.asarray(-jnp.inf, f.dtype)

    from repro.agents.polynomial import PolynomialFamily  # agents -> jax only

    if isinstance(family, PolynomialFamily):
        phi_t, ginv = _poly_projector(xcols, family.degree, family.ridge)

        def project(i, p_old, f_hat):
            del p_old  # closed form, at the family's own precision
            p_new = jnp.matmul(
                ginv[i], jnp.matmul(phi_t[i], f_hat, precision=_HIGHEST),
                precision=_HIGHEST)
            return p_new, jnp.matmul(p_new, phi_t[i], precision=_HIGHEST)
    else:
        def project(i, p_old, f_hat):
            p_new = family.fit(p_old, xcols[i], f_hat)
            return p_new, family.predict(p_new, xcols[i])

    def update_agent(slot, carry):
        params, f, rs, a0, m_inv, s, eta, led, tps = carry
        i = slot if order is None else order[slot]
        eta0 = eta

        # --- probe: gradient + the whole back-search schedule ---
        if idx is None:
            if uk:
                etas, cross, _, gnorm = sweep_ops.probe_sweep(
                    rs, m_inv, s, eta, i, steps)
                g_unit = ((2.0 / m) * s[i] / gnorm) * cross
            else:
                g = gradient.cached_row_gradient(s, rs, i)
                gnorm = jnp.linalg.norm(g) + 1e-30
                g_unit = g / gnorm
                # R @ g_unit = (2 s_i / (m gnorm)) * (A0 @ s): zero-pass probe
                p = (2.0 * s[i] / (m * gnorm)) * (a0 @ s)
                gg = jnp.vdot(g_unit, g_unit, precision=_HIGHEST)
                etas = sweep_ref.probe_etas_closed(
                    m_inv, s, eta, i, steps, p,
                    jnp.zeros((), f.dtype), gg / (2.0 * m))
        else:
            r_i = y - f[i]
            g = (2.0 / n) * (s[i] * s[i]) * r_i
            g = g.at[idx].add(
                gradient.cached_row_gradient(s, rs, i, exclude_self=True))
            gnorm = jnp.linalg.norm(g) + 1e-30
            g_unit = g / gnorm
            g_sub = g_unit[idx]
            p = covstate.row_product(g_sub, rs, use_kernel=uk) / m
            c1 = jnp.vdot(r_i, g_unit, precision=_HIGHEST)  # exact-diag cross
            etas = sweep_ref.probe_etas_closed(
                m_inv, s, eta, i, steps, p.at[i].set(0.0),
                -c1 / n, 0.5 / jnp.asarray(n, f.dtype))

        improved = etas > eta0
        kstar = jnp.argmax(improved)            # first improving step wins
        step = jnp.where(jnp.any(improved), steps[kstar],
                         jnp.zeros((), f.dtype))

        # --- projection onto H_i ---
        f_hat = f[i] + step * g_unit
        p_old = jax.tree.map(lambda t: t[i], params)
        p_new, f_new = project(i, p_old, f_hat)

        # --- fused accept/commit ---
        r_new = y - f_new
        r_new_sub = tp.relay_row(r_new if idx is None else r_new[idx], i)
        if fl is not None:
            # wire-view corruption (see _sweep_incremental): the delivered
            # row may arrive flipped; the sender's own state stays clean
            r_new_sub = faults_trace.corrupt(fl, r_new_sub, rnd, i)
        delta = r_new_sub - rs[i]
        if idx is None:
            diag_keep = jnp.ones((), f.dtype)
            diag_add = jnp.zeros((), f.dtype)
        else:
            ddiag_acc = tp.relay_scalar(
                jnp.vdot(r_new, r_new, precision=_HIGHEST) / n, i) - a0[i, i]
            diag_keep = jnp.zeros((), f.dtype)
            diag_add = 0.5 * ddiag_acc
        threshold = eta0 if cfg.accept_reject else neg_inf
        if fl is not None:
            # drop/straggle/crash fold into the commit's can_tx coefficient:
            # an undelivered candidate is an exact no-op commit
            can_tx, led = faults_inject.gate_broadcast(fl, led, live, bcosts,
                                                       i, alive[i], rnd,
                                                       budget)
            tps = obs_taps.tap_fault_retries(tps, cfg.obs, fl, rnd, i, alive[i])
        elif budget is not None:
            can_tx, led = transport_lib.gate_broadcast(led, live, bcosts, i,
                                                       budget)
            tps = obs_taps.tap_budget_reject(tps, cfg.obs, can_tx)
        else:
            can_tx = jnp.bool_(True)
        # uk=False calls the oracle directly (no nested-jit call boundary in
        # the loop body — XLA fuses the commit chain into the surrounding
        # program); uk=True pays the boundary to reach the Pallas kernel
        if uk:
            m_inv, s, u_eff, accept, _ = sweep_ops.commit_sweep(
                rs, m_inv, s, eta, i, delta, diag_keep, diag_add, threshold,
                can_tx)
        else:
            m_inv, s, u_eff, accept, _ = sweep_ref.commit_sweep_ref(
                rs, m_inv, s, eta, i, delta, diag_keep, diag_add, threshold,
                can_tx)
        eta = jnp.sum(s)
        tps = obs_taps.tap_accept(tps, cfg.obs, i, accept)

        p_i = jax.tree.map(lambda new, old: jnp.where(accept, new, old),
                           p_new, p_old)
        params = jax.tree.map(lambda t, u_: t.at[i].set(u_), params, p_i)
        f = f.at[i].set(jnp.where(accept, f_new, f[i]))
        a0 = a0.at[i, :].add(u_eff).at[:, i].add(u_eff)   # u_eff = 0 on reject
        rs = rs.at[i].set(jnp.where(accept, r_new_sub, rs[i]))
        return params, f, rs, a0, m_inv, s, eta, led, tps

    params, f, _, _, _, _, _, ledger, taps = jax.lax.fori_loop(
        0, d, update_agent,
        (params, f, cs0.r_sub, cs0.a0, cs0.m_inv, cs0.s, cs0.eta_tilde,
         ledger, taps0))
    return params, f, ledger, taps


def _weights(f: jnp.ndarray, y: jnp.ndarray, cfg: ICOAConfig, key: jax.Array,
             alive: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Ensemble weights from what the agents can see (robust iff protected).

    `alive` (static-shaped (D,) bool, crash-schedule runs only) restricts the
    combination to the surviving agents: dead agents get weight exactly 0 and
    the optimum is re-solved over the survivors (ensemble.surviving_weights).
    Crashes and minimax protection are mutually exclusive
    (faults.require_fault_engine), so the robust branch never sees `alive`.
    """
    r = y[None, :] - f
    if cfg.alpha > 1.0:
        a0 = cov.subsampled_covariance(key, r, cfg.alpha, use_kernel=cfg.use_kernel)
    else:
        a0 = cov.gram(r, use_kernel=cfg.use_kernel)
    if cfg.delta > 0.0:
        return minimax.robust_weights(a0, cfg.delta, steps=cfg.minimax_steps, lr=cfg.minimax_lr)
    if alive is not None:
        return ensemble.surviving_weights(a0, alive)
    return ensemble.optimal_weights(a0)


def ensemble_predict(family, params: Any, weights: jnp.ndarray, xcols: jnp.ndarray) -> jnp.ndarray:
    preds = jax.vmap(family.predict)(params, xcols)
    return ensemble.combine(weights, preds)


def converged_record(eta: jnp.ndarray, eps: float) -> jnp.ndarray:
    """Record index where the serial eps rule stops, from a full eta history.

    `run` breaks after recording sweep k (record k >= 2) when
    |eta[k] - eta[k-1]| < eps, comparing post-sweep records only (record 0 is
    the non-cooperative init, record 1 has no predecessor sweep).  Compiled
    schedules are static, so they execute every sweep regardless — this
    closed form reports where `fit()` WOULD have truncated the history.
    Traceable (jnp ops only): batches under the trial vmap.
    """
    eta = jnp.asarray(eta)
    last = eta.shape[0] - 1
    if eta.shape[0] < 3:
        return jnp.asarray(last, jnp.int32)
    hit = jnp.abs(eta[2:] - eta[1:-1]) < eps
    first = jnp.argmax(hit) + 2
    return jnp.where(jnp.any(hit), first, last).astype(jnp.int32)


def run_scan(family, cfg: ICOAConfig, xcols: jnp.ndarray, y: jnp.ndarray,
             xcols_test: jnp.ndarray, y_test: jnp.ndarray, seed):
    """Fully-traceable ICOA run: the Monte-Carlo building block.

    Same math and key discipline as `run` — init from PRNGKey(seed), record,
    then per sweep `key, k1, k2 = split(key, 3)`, sweep with k1, record with
    k2 — but the outer loop is a static `lax.scan` over cfg.n_sweeps (no eps
    early exit: a data-dependent break cannot be staged) and every recorded
    quantity stays a jnp array.  `seed` may be a traced integer, so
    `jax.vmap(run_scan, ...)` executes a whole batch of independent trials as
    ONE compiled program (api.batch_fit; DESIGN.md §6).

    Returns (params, f, weights, hist) with hist arrays of length
    cfg.n_sweeps + 1 (record 0 = the non-cooperative init, like `run`), plus
    hist["converged_at"] — the record index where `run`'s eps rule would have
    stopped (the static schedule cannot break early, but it can report) —
    and hist["bytes"], the measured per-sweep ledger bytes (record 0 = 0).
    With cfg.obs set, hist["taps"] is the dict of stacked per-sweep tap
    series (length cfg.n_sweeps — sweep k aligns with record k+1); {} when
    obs is off.
    """
    d = xcols.shape[0]
    seed = jnp.asarray(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), d)
    state0 = init_state(family, keys, xcols, y)
    fl = cfg.transport.faults if cfg.transport is not None else None
    crashes = fl is not None and bool(fl.crash)

    rec_obs = cfg.obs is not None and ("eta" in cfg.obs.taps
                                       or "s" in cfg.obs.taps)

    def record(params, f, k, alive=None):
        w = _weights(f, y, cfg, k, alive)
        train = jnp.mean((y - ensemble.combine(w, f)) ** 2)
        pred = ensemble_predict(family, params, w, xcols_test)
        test = jnp.mean((y_test - pred) ** 2)
        if rec_obs:
            # expand _eta_tilde_sub so the tap shares the recorded Gram: the
            # expression tree is identical to the off-mode one (XLA CSEs the
            # duplicate solve), so History.eta is bitwise unchanged and the
            # "eta" tap matches it exactly
            a0r = _subsampled_a0(f, y, None, cfg)
            eta = 1.0 / sanitize.check_nonzero(
                ensemble.eta_tilde(a0r),
                "icoa.run_scan record: eta_tilde (eta = 1/eta_tilde)")
            rtaps = obs_taps.record_taps(cfg.obs, eta,
                                         ensemble.solve_vec(a0r))
        else:
            eta = 1.0 / sanitize.check_nonzero(
                _eta_tilde_sub(f, y, None, cfg),
                "icoa.run_scan record: eta_tilde (eta = 1/eta_tilde)")
            rtaps = {}
        return w, train, test, eta, rtaps

    key0 = jax.random.PRNGKey(seed + 1)
    w0, tr0, te0, et0, _ = record(state0.params, state0.f, key0)

    def step(carry, r):
        params, f, key, led = carry
        key, k1, k2 = jax.random.split(key, 3)
        params, f, _, led2, etaps = sweep(family, cfg, params, f, xcols, y,
                                          k1, led, r)
        alive = faults_trace.alive_at(fl, d, r) if crashes else None
        w, tr, te, et, rtaps = record(params, f, k2, alive)
        return (params, f, key, led2), (w, tr, te, et,
                                        led2.spent - led.spent,
                                        {**etaps, **rtaps})

    (params, f, _, _), (ws, trs, tes, ets, bts, taps) = jax.lax.scan(
        step, (state0.params, state0.f, key0, Ledger.empty()),
        jnp.arange(cfg.n_sweeps))
    hist = {
        "train_mse": jnp.concatenate([tr0[None], trs]),
        "test_mse": jnp.concatenate([te0[None], tes]),
        "eta": jnp.concatenate([et0[None], ets]),
        "bytes": jnp.concatenate([jnp.zeros_like(bts[:1]), bts]),
    }
    hist["converged_at"] = converged_record(hist["eta"], cfg.eps)
    # scan already stacked each tap over the sweep axis (row k = sweep k,
    # i.e. History record k+1); keep them out of the History arrays
    hist["taps"] = taps
    return params, f, ws[-1], hist


def run(family, cfg: ICOAConfig, xcols: jnp.ndarray, y: jnp.ndarray,
        xcols_test: Optional[jnp.ndarray] = None, y_test: Optional[jnp.ndarray] = None,
        seed: int = 0):
    """Full ICOA run; returns (state, weights, history dict of per-sweep errors)."""
    sanitize.validate_mode(cfg.checks, "ICOAConfig.checks")
    # checks="raise" functionalizes the sweep's check sites via checkify and
    # throws on the first failed check (DESIGN.md §9.2); "off" is this exact
    # jitted sweep, bit for bit.  checkify flattens every argument, so the
    # static family/cfg pair is bound by partial, never traced.
    sweep_fn = partial(sweep, family, cfg)
    if cfg.checks == "raise":
        sweep_fn = sanitize.checked(sweep_fn)
    d = xcols.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(seed), d)
    state = init_state(family, keys, xcols, y)
    fl = cfg.transport.faults if cfg.transport is not None else None
    crashes = fl is not None and bool(fl.crash)
    hist = {"train_mse": [], "test_mse": [], "eta": [], "bytes": [0.0]}
    eta_prev = jnp.inf
    key = jax.random.PRNGKey(seed + 1)
    ledger = Ledger.empty()
    rec_obs = cfg.obs is not None and ("eta" in cfg.obs.taps
                                       or "s" in cfg.obs.taps)
    tap_rows = []

    def record(params, f, key, alive=None):
        w = _weights(f, y, cfg, key, alive)
        train_mse = jnp.mean((y - ensemble.combine(w, f)) ** 2)
        hist["train_mse"].append(float(train_mse))
        if xcols_test is not None:
            pred = ensemble_predict(family, params, w, xcols_test)
            hist["test_mse"].append(float(jnp.mean((y_test - pred) ** 2)))
        if rec_obs:
            # share the recorded Gram with the taps (see run_scan.record)
            a0r = _subsampled_a0(f, y, None, cfg)
            eta = 1.0 / ensemble.eta_tilde(a0r)
            hist["eta"].append(float(eta))
            rtaps = obs_taps.record_taps(cfg.obs, eta,
                                         ensemble.solve_vec(a0r))
        else:
            hist["eta"].append(float(1.0 / _eta_tilde_sub(f, y, None, cfg)))
            rtaps = {}
        return w, rtaps

    weights, _ = record(state.params, state.f, key)
    for r in range(cfg.n_sweeps):
        key, k1, k2 = jax.random.split(key, 3)
        params, f, _, led2, etaps = sweep_fn(state.params, state.f, xcols, y,
                                             k1, ledger,
                                             jnp.asarray(r, jnp.int32))
        hist["bytes"].append(float(led2.spent - ledger.spent))
        ledger = led2
        state = ICOAState(params=params, f=f, key=key)
        alive = faults_trace.alive_at(fl, d, r) if crashes else None
        weights, rtaps = record(params, f, k2, alive)
        if cfg.obs is not None and cfg.obs.enabled:
            tap_rows.append({**etaps, **rtaps})
        eta_now = hist["eta"][-1]
        if abs(eta_prev - eta_now) < cfg.eps:
            break
        eta_prev = eta_now
    hist["taps"] = obs_taps.stack_tap_rows(tap_rows)
    return state, weights, hist
