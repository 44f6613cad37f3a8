"""Distributed ICOA under shard_map: agents live on a mesh axis.

This is the paper's system realised as a collective schedule (DESIGN.md §3.1):

  * the data are ATTRIBUTE-SHARDED — each device holds only its agent's
    covariate columns (xcols in_spec P("agents")); attribute data never
    crosses the wire, matching the paper's confidentiality restriction;
  * the ONLY inter-agent traffic is residuals: one `all_gather` over the
    "agents" axis per agent update — O(N * D^2) per sweep, the paper's ICOA
    figure (Fig. 2, right);
  * Minimax Protection (alpha > 1) gathers only an N/alpha subsample plus the
    D local variance scalars, shrinking the payload by alpha — the paper's
    transmission/performance trade-off as a first-class sharding knob; at
    alpha <= 1 the bodies index nothing and move the full-length rows as
    they are (`_subsample`, core.icoa's `idx = None` rule);
  * the D x D covariance algebra is replicated (it is tiny); the projection
    re-training runs everywhere but only the owning agent keeps its result
    (a `where` on axis_index), so there is no parameter traffic either.

The gradient uses the closed form (core/gradient.py) — cheap and local once
residuals are gathered.

`cfg.engine` picks the replicated D x D compute path (DESIGN.md §5):
"incremental" (default) carries a core.covstate.CovState through the agent
loop — one residual gather at sweep start, one candidate-row broadcast per
update, rank-2 SMW algebra everywhere (so its wire traffic IS the
row_broadcast schedule's 2*m*D per sweep); "dense" is the paper-faithful
recompute-everything oracle above.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro import transport as transport_lib
from repro.analysis import sanitize
from repro.faults import inject as faults_inject
from repro.faults import trace as faults_trace
from repro.core import baselines
from repro.core import covariance as cov
from repro.core import covstate
from repro.core import ensemble, gradient, minimax
from repro.core.icoa import ICOAConfig
from repro.obs import taps as obs_taps
from repro.transport import Ledger
from repro.transport import ledger as ledger_mod

__all__ = ["make_agent_mesh", "distributed_sweep", "run_distributed",
           "run_scan_distributed", "run_averaging_distributed",
           "run_averaging_scan_distributed", "run_refit_distributed",
           "run_refit_scan_distributed"]


def make_agent_mesh(n_agents: int) -> Mesh:
    devs = jax.devices()
    if len(devs) < n_agents:
        raise ValueError(
            f"need >= {n_agents} devices for {n_agents} agents, have {len(devs)} "
            "(launch with XLA_FLAGS=--xla_force_host_platform_device_count=D)")
    return Mesh(__import__("numpy").array(devs[:n_agents]), ("agents",))


def _record_gram(mesh: Mesh, use_kernel: bool):
    """(D, N) residuals, one row per agent's device -> the (D, D) Gram of the
    record's eta (a diagnostic, not the paper's traffic).  XLA cannot
    partition a Mosaic kernel, so the Gram runs inside shard_map as the
    sweep's do: every device gathers the rows and computes it on its copy."""

    def body(r_local):
        return cov.gram(jax.lax.all_gather(r_local[0], "agents"),
                        use_kernel=use_kernel)

    return jax.shard_map(body, mesh=mesh, in_specs=P("agents"),
                         out_specs=P(), check_vma=False)


def _gathered_a0(f_sub_all: jnp.ndarray, y_sub: jnp.ndarray, diag_all: jnp.ndarray,
                 alpha: float, tp=None) -> jnp.ndarray:
    """A0 from gathered (possibly subsampled) residuals + exact local diags.

    `tp` (a transport.Transport) codes every gathered payload — residual
    rows and, under the split, the diag scalars — with straight-through
    gradients, so the replicated objective sees what actually crossed the
    wire.  Identity transports short-circuit (bit-for-bit legacy parity)."""
    r_sub = y_sub[None, :] - f_sub_all
    if tp is not None:
        r_sub = tp.relay_rows_st(r_sub)
    a0 = cov.gram(r_sub)
    if alpha > 1.0:
        if tp is not None:
            diag_all = tp.relay_scalars_st(diag_all)
        a0 = a0 - jnp.diag(jnp.diag(a0)) + jnp.diag(diag_all)
    return a0


def _subsample(cfg: ICOAConfig, key, n: int):
    """The instances each party sends per residual gather, as both sweep
    bodies index them: (idx, m, take).  alpha > 1 draws Minimax Protection's
    random subsample (`cov.subsample_indices` on the second half of
    `split(key)`, the same key on every device); otherwise idx is None, as
    in core.icoa, m = n and `take` is the identity, so the bodies move the
    full-length rows with no gather or scatter."""
    if cfg.alpha > 1.0:
        idx = cov.subsample_indices(jax.random.split(key)[1], n, cfg.alpha)
        return idx, idx.shape[0], lambda a: a[idx]
    return None, n, lambda a: a


def _add_at(f, idx, v):
    """f with v added at the subsampled positions (everywhere at idx None:
    the same sum, though a backend may fuse the multiply that forms v into
    the add, as XLA's CPU backend does)."""
    return f + v if idx is None else f.at[idx].add(v)


def _sweep_body(cfg: ICOAConfig, tp, family, xcol, y, f_local, params_local,
                key, ledger, round_):
    """Runs INSIDE shard_map. Shapes (local): xcol (1,N,C); f_local (1,N)."""
    del round_   # fault injection requires the carried-CovState body below
    d = jax.lax.psum(1, "agents")
    me = jax.lax.axis_index("agents")
    n = y.shape[0]

    idx, m, take = _subsample(cfg, key, n)
    ledger_mod.ensure_sweep_capacity(tp, cfg.n_sweeps, m,
                                     split=cfg.alpha > 1.0,
                                     row_wise=cfg.row_broadcast, ledger=ledger)
    ledger = ledger.charge(ledger_mod.icoa_sweep_cost(
        tp, m, split=cfg.alpha > 1.0, row_wise=cfg.row_broadcast))
    # taps are replicated D x D-side algebra (out_spec P() broadcasts them);
    # the static topology size keeps shapes un-traced
    taps0 = obs_taps.init_engine_taps(cfg.obs, tp.topology.n_agents,
                                      f_local.dtype)

    def eta_tilde_of(f_sub_all, diag_all):
        a0 = _gathered_a0(f_sub_all, take(y), diag_all, cfg.alpha, tp)
        if cfg.delta > 0.0:
            a = jax.lax.stop_gradient(minimax.robust_weights(
                a0, cfg.delta, steps=cfg.minimax_steps, lr=cfg.minimax_lr))
            return -minimax.robust_objective(a, a0, cfg.delta)
        return ensemble.eta_tilde(a0)

    def agent_update(i, carry):
        f_local, params_local, f_cache, diag_cache, tps = carry
        if cfg.row_broadcast:
            # §Perf C: rows only change when their owner updates, so the
            # carried gather stays current — no re-gather needed
            f_sub_all, diag_all = f_cache, diag_cache
        else:
            # paper-faithful schedule: every agent re-transmits its residual
            # before every update — O(N*D) wire bytes per update
            f_sub_all = jax.lax.all_gather(take(f_local[0]), "agents")  # (D, m)
            diag_all = jax.lax.all_gather(
                jnp.mean((y - f_local[0]) ** 2), "agents")              # (D,) local variances

        # replicated D x D algebra: gradient of the (protected) objective
        # w.r.t. agent i's subsampled predictions
        g_sub = jax.grad(lambda fi: eta_tilde_of(f_sub_all.at[i].set(fi), diag_all))(
            f_sub_all[i])
        gnorm = jnp.linalg.norm(g_sub) + 1e-30
        g_unit = g_sub / gnorm
        eta0 = eta_tilde_of(f_sub_all, diag_all)

        def cond(state):
            step, probes = state
            improved = eta_tilde_of(
                f_sub_all.at[i].set(f_sub_all[i] + step * g_unit), diag_all) > eta0
            return jnp.logical_and(~improved, probes < cfg.max_probes)

        step0 = cfg.step0 * jnp.sqrt(jnp.asarray(m, jnp.float32))
        step, probes = jax.lax.while_loop(
            cond, lambda s: (s[0] * cfg.backtrack, s[1] + 1),
            (step0, jnp.asarray(0, jnp.int32)))
        step = jnp.where(probes >= cfg.max_probes, 0.0, step)

        # scatter the gradient step back to full-length targets: only the
        # subsampled positions move (the paper re-fits on the perturbed vector)
        f_hat_full = _add_at(f_local[0], idx, step * g_unit)

        # projection onto H_i — executed everywhere, kept only by agent i
        # (xcol is the agent's OWN columns: no attribute data moved)
        new_p = family.fit(jax.tree.map(lambda t: t[0], params_local), xcol[0], f_hat_full)
        new_f = family.predict(new_p, xcol[0])
        # accept/reject after projection (see core.icoa.sweep): agent i checks
        # its own post-projection objective on the shared subsample
        my_sub_new = jax.lax.psum(
            jnp.where(me == i, take(new_f), jnp.zeros_like(take(new_f))),
            "agents")
        eta_post = eta_tilde_of(f_sub_all.at[i].set(my_sub_new), diag_all)
        accept = eta_post > eta0
        tps = obs_taps.tap_accept(tps, cfg.obs, i, accept)
        new_p = jax.tree.map(lambda new, old: jnp.where(accept, new, old[0]),
                             new_p, params_local)
        new_f = jnp.where(accept, new_f, f_local[0])
        is_me = (me == i)
        params_local = jax.tree.map(
            lambda old, new: jnp.where(is_me, new[None], old), params_local, new_p)
        f_local = jnp.where(is_me, new_f[None], f_local)
        if cfg.row_broadcast:
            # broadcast ONLY agent i's accepted row: one masked psum = O(N/alpha)
            row = jax.lax.psum(
                jnp.where(is_me, take(new_f), jnp.zeros_like(take(new_f))),
                "agents")
            dnew = jax.lax.psum(jnp.where(is_me, jnp.mean((y - new_f) ** 2), 0.0),
                                "agents")
            f_cache = f_cache.at[i].set(row)
            diag_cache = diag_cache.at[i].set(dnew)
        return f_local, params_local, f_cache, diag_cache, tps

    # one initial gather (row_broadcast keeps it current; the paper-faithful
    # path re-gathers inside the loop and ignores the carry)
    f_cache0 = jax.lax.all_gather(take(f_local[0]), "agents")
    diag_cache0 = jax.lax.all_gather(jnp.mean((y - f_local[0]) ** 2), "agents")
    if "codec_error" in taps0:
        # the dense schedule re-codes every probe; report the sweep-start
        # gather's round trip (what the incremental body's CovState absorbs)
        sent0 = take(y)[None, :] - f_cache0
        taps0 = obs_taps.tap_codec_error(taps0, cfg.obs, sent0,
                                         tp.relay_rows(sent0))
    f_local, params_local, f_cache, diag_cache, taps = jax.lax.fori_loop(
        0, d, agent_update, (f_local, params_local, f_cache0, diag_cache0,
                             taps0))

    # final weights from what agents can see
    if cfg.row_broadcast:
        f_sub_all, diag_all = f_cache, diag_cache
    else:
        f_sub_all = jax.lax.all_gather(take(f_local[0]), "agents")
        diag_all = jax.lax.all_gather(jnp.mean((y - f_local[0]) ** 2), "agents")
    a0 = _gathered_a0(f_sub_all, take(y), diag_all, cfg.alpha, tp)
    if cfg.delta > 0.0:
        w = minimax.robust_weights(a0, cfg.delta, steps=cfg.minimax_steps, lr=cfg.minimax_lr)
    else:
        w = ensemble.optimal_weights(a0)
    return f_local, params_local, w, ledger, taps


def _sweep_body_incremental(cfg: ICOAConfig, tp, family, xcol, y, f_local,
                            params_local, key, ledger, round_):
    """Runs INSIDE shard_map: the rank-2 CovState engine.

    Identical math to `_sweep_body` (same gradient via the cached closed form,
    same back-search, same accept rule, same final weights), but the D x D
    algebra is carried: the full-residual gather happens ONCE per sweep (that
    rebuild is the drift-bounding refresh) and each update moves only the
    candidate row — one masked psum of N/alpha floats plus one variance
    scalar.  Probes are O(D^2) SMW evaluations off the carried state instead
    of O(m*D^2) Gram rebuilds + O(D^3) solves.

    Transport: the gather and the candidate broadcasts pass the codec relay
    before entering the carried CovState; the ledger charges the measured
    payload bytes, and a byte budget gates per-agent broadcasts exactly as
    the local engine does (core.icoa._sweep_incremental) — the gating/order
    state is replicated D x D algebra, so every device takes the same branch.

    Fault semantics (tp.faults set) mirror core.icoa._sweep_incremental —
    alive-only gather charge, seeded drop/straggle gating with retransmit
    bytes, wire-view corruption of the delivered candidate row, survivors-only
    final weights under a crash schedule — with `round_` (replicated int32)
    as the event coordinate, so both backends replay the SAME fault trace.
    """
    d = jax.lax.psum(1, "agents")
    me = jax.lax.axis_index("agents")
    n = y.shape[0]
    fl = tp.faults
    rnd = jnp.asarray(round_, jnp.int32)

    idx, m, take = _subsample(cfg, key, n)
    split = cfg.alpha > 1.0          # Sec 4.1 exact-local-diagonal split
    protected = cfg.delta > 0.0
    uk = cfg.use_kernel
    budget = tp.byte_budget
    ledger_mod.ensure_sweep_capacity(
        tp, cfg.n_sweeps, m, split=split, row_wise=True, ledger=ledger,
        retries=0 if fl is None else fl.max_retries)

    # the engine's ONLY full gather: residual rows + local variances, once
    f_sub_all = jax.lax.all_gather(take(f_local[0]), "agents")      # (D, m)
    sent0 = take(y)[None, :] - f_sub_all
    r_sub0 = tp.relay_rows(sent0)
    if split:
        diag0 = tp.relay_scalars(
            jax.lax.all_gather(jnp.mean((y - f_local[0]) ** 2), "agents"))
        cs0 = covstate.build(r_sub0, exact_diag=diag0, use_kernel=uk)
    else:
        cs0 = covstate.build(r_sub0, use_kernel=uk)
    # taps are replicated algebra (out_spec P() broadcasts the dict); static
    # topology size, NOT the psum'd d, keeps the accumulator shapes un-traced
    taps0 = obs_taps.init_engine_taps(cfg.obs, tp.topology.n_agents,
                                      f_local.dtype)
    taps0 = obs_taps.tap_codec_error(taps0, cfg.obs, sent0, r_sub0)

    # greedy priority probes at THIS body's back-search scale — sqrt(m) in
    # f32, vs sqrt(n) in the local engine — mirroring the pre-existing step0
    # conventions of the two sweep bodies, so a budgeted greedy order can
    # differ across backends when alpha > 1 (as their trajectories already do)
    if fl is not None:
        # static topology size, NOT the psum'd d: alive_at needs a shape
        alive = faults_trace.alive_at(fl, tp.topology.n_agents, rnd)
        live, order, bcosts, ledger = faults_inject.budget_setup(
            tp, cs0, ledger, m, split,
            step0=cfg.step0 * jnp.sqrt(jnp.asarray(m, jnp.float32)),
            alive=alive)
    else:
        alive = None
        live, order, bcosts, ledger = transport_lib.budget_setup(
            tp, cs0, ledger, m, split,
            step0=cfg.step0 * jnp.sqrt(jnp.asarray(m, jnp.float32)))

    def robust_probe(cs, i, u):
        return covstate.robust_eta_probe(cs, i, u, cfg.delta,
                                         cfg.minimax_steps, cfg.minimax_lr)

    def agent_update(slot, carry):
        f_local, params_local, cs, led, tps = carry
        i = slot if order is None else order[slot]

        if protected:
            v = minimax.robust_weights(cs.a0, cfg.delta, steps=cfg.minimax_steps,
                                       lr=cfg.minimax_lr,
                                       a_init=cs.s / jnp.sum(cs.s))
            eta0 = -minimax.robust_objective(v, cs.a0, cfg.delta)
        else:
            v = cs.s
            eta0 = cs.eta_tilde

        # closed-form gradient w.r.t. agent i's subsampled predictions off the
        # cached solve (the dense body's autodiff holds diag_all fixed under
        # the split, hence exclude_self there)
        g_sub = gradient.cached_row_gradient(v, cs.r_sub, i, exclude_self=split)
        gnorm = jnp.linalg.norm(g_sub) + 1e-30
        g_unit = g_sub / gnorm

        p = covstate.row_product(g_unit, cs.r_sub, use_kernel=uk) / m

        def u_of(step):
            w = -step * p
            if split:
                return w.at[i].set(0.0)    # probes hold the exact diag fixed
            return w.at[i].add(step * step / (2.0 * m))   # ||g_unit|| = 1

        def probe_obj(step):
            u = u_of(step)
            if protected:
                return robust_probe(cs, i, u)
            return covstate.eta_probe(cs, i, u)

        def cond(state):
            step, probes = state
            return jnp.logical_and(~(probe_obj(step) > eta0),
                                   probes < cfg.max_probes)

        step0 = cfg.step0 * jnp.sqrt(jnp.asarray(m, jnp.float32))
        step, probes = jax.lax.while_loop(
            cond, lambda s: (s[0] * cfg.backtrack, s[1] + 1),
            (step0, jnp.asarray(0, jnp.int32)))
        step = jnp.where(probes >= cfg.max_probes, 0.0, step)

        # scatter the step to full-length targets; projection runs everywhere,
        # only the owner keeps it (no attribute data moved)
        f_hat_full = _add_at(f_local[0], idx, step * g_unit)
        new_p = family.fit(jax.tree.map(lambda t: t[0], params_local),
                           xcol[0], f_hat_full)
        new_f = family.predict(new_p, xcol[0])

        # broadcast the CANDIDATE row + its variance: the per-update traffic
        cand_sub = jax.lax.psum(
            jnp.where(me == i, take(new_f), jnp.zeros_like(take(new_f))),
            "agents")
        cand_diag = tp.relay_scalar(jax.lax.psum(
            jnp.where(me == i, jnp.mean((y - new_f) ** 2), 0.0), "agents"), i)
        r_cand = tp.relay_row(take(y) - cand_sub, i)
        if fl is not None:
            # wire-view corruption (see core.icoa._sweep_incremental): the
            # delivered row may arrive flipped; the owner's f stays clean
            r_cand = faults_trace.corrupt(fl, r_cand, rnd, i)
        delta_sub = r_cand - cs.r_sub[i]
        # accept is judged with the diag held fixed (exactly as the dense body
        # scores eta_post against the OLD diag_all); the commit then moves it
        u_eval = covstate.row_update_vector(
            cs, i, delta_sub, ddiag=jnp.asarray(0.0) if split else None,
            use_kernel=uk)
        obj_post = robust_probe(cs, i, u_eval) if protected \
            else covstate.eta_probe(cs, i, u_eval)
        accept = obj_post > eta0

        if fl is not None:
            ok, led = faults_inject.gate_broadcast(fl, led, live, bcosts, i,
                                                   alive[i], rnd, budget)
            accept = jnp.logical_and(accept, ok)
            tps = obs_taps.tap_fault_retries(tps, cfg.obs, fl, rnd, i,
                                             alive[i])
        elif budget is not None:
            can_tx, led = transport_lib.gate_broadcast(led, live, bcosts, i,
                                                       budget)
            accept = jnp.logical_and(accept, can_tx)
            tps = obs_taps.tap_budget_reject(tps, cfg.obs, can_tx)
        tps = obs_taps.tap_accept(tps, cfg.obs, i, accept)

        new_p = jax.tree.map(lambda new, old: jnp.where(accept, new, old[0]),
                             new_p, params_local)
        new_f = jnp.where(accept, new_f, f_local[0])
        is_me = (me == i)
        params_local = jax.tree.map(
            lambda old, new: jnp.where(is_me, new[None], old), params_local, new_p)
        f_local = jnp.where(is_me, new_f[None], f_local)

        if split:
            u_commit = u_eval.at[i].set(0.5 * (cand_diag - cs.a0[i, i]))
        else:
            u_commit = u_eval
        cs_next = covstate.apply_row_update(cs, i, r_cand, u_commit)
        cs = jax.tree.map(lambda a, b: jnp.where(accept, a, b), cs_next, cs)
        return f_local, params_local, cs, led, tps

    f_local, params_local, cs, ledger, taps = jax.lax.fori_loop(
        0, d, agent_update, (f_local, params_local, cs0, ledger, taps0))

    # final weights from the carried covariance — no re-gather needed
    if protected:
        w = minimax.robust_weights(cs.a0, cfg.delta, steps=cfg.minimax_steps,
                                   lr=cfg.minimax_lr)
    elif fl is not None and fl.crash:
        # survivors-only combination: dead agents' stale rows stay in the
        # CovState but are masked out of the served ensemble (DESIGN.md §12)
        w = ensemble.surviving_weights(cs.a0, alive)
    else:
        w = ensemble.optimal_weights(cs.a0)
    return f_local, params_local, w, ledger, taps


def _sweep_shmap(mesh: Mesh, cfg: ICOAConfig, family):
    """The shard_map'd sweep WITHOUT the jit wrapper: traceable from inside
    an enclosing jit/scan (the compiled Monte-Carlo batch path)."""
    d = mesh.devices.size
    tp = (cfg.transport or transport_lib.default_transport(d)).validate_for(d)
    transport_lib.require_budget_engine(tp, cfg.engine)
    faults_inject.require_fault_engine(tp, cfg)
    # "fused" is a single-host engine (its fusion lives inside one device's
    # agent loop); across the mesh its row-wise schedule IS the incremental
    # body, so it maps there rather than to the dense all-gather body
    body_fn = (_sweep_body_incremental if cfg.engine in ("incremental", "fused")
               else _sweep_body)
    body = partial(body_fn, cfg, tp, family)
    sm = jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(P("agents"), P(), P("agents"), P("agents"), P(), P(), P()),
        # the trailing P() is a tree PREFIX for the tap dict: every leaf of
        # the (possibly empty) replicated tap pytree is unsharded
        out_specs=(P("agents"), P("agents"), P(), P(), P()),
    )

    def sweep(xcols, y, f, params, key, ledger, round_=None):
        # the scope is open while shard_map traces the body, so the relay /
        # covstate check sites inside it insert iff cfg.checks says so
        # (checkify discharges through shard_map).  Every check on this
        # backend must live INSIDE the body: in-body errors leave the shmap
        # with a per-device axis, and checkify cannot merge them with a
        # scalar check added out here (shape-mismatched error select)
        rnd = jnp.asarray(0 if round_ is None else round_, jnp.int32)
        with sanitize.sanitize_scope(cfg.checks):
            f, params, w, ledger, taps = sm(xcols, y, f, params, key, ledger,
                                            rnd)
        return f, params, w, ledger, taps

    return sweep


def distributed_sweep(mesh: Mesh, cfg: ICOAConfig, family):
    """Compiled shard_map sweep:
    (xcols, y, f, params, key, ledger) -> (f, params, w, ledger, taps)."""
    return jax.jit(_sweep_shmap(mesh, cfg, family))


def run_distributed(family, cfg: ICOAConfig, xcols: jnp.ndarray, y: jnp.ndarray,
                    xcols_test: Optional[jnp.ndarray] = None,
                    y_test: Optional[jnp.ndarray] = None,
                    mesh: Optional[Mesh] = None, seed: int = 0):
    """Full distributed ICOA run; mirrors core.icoa.run's return contract —
    same history keys (train_mse / test_mse / eta) and the same eps
    early-stopping rule on successive eta values."""
    d = xcols.shape[0]
    mesh = mesh or make_agent_mesh(d)
    keys = jax.random.split(jax.random.PRNGKey(seed), d)
    params = jax.vmap(lambda k, x: family.fit(family.init(k), x, y))(keys, xcols)
    f = jax.vmap(family.predict)(params, xcols)

    sanitize.validate_mode(cfg.checks, "ICOAConfig.checks")
    sweep_fn = distributed_sweep(mesh, cfg, family)
    if cfg.checks == "raise":
        # functionalize the check sites and throw on the first failure
        sweep_fn = sanitize.checked(sweep_fn)
    hist = {"train_mse": [], "test_mse": [], "eta": [], "bytes": [0.0]}
    record_gram = jax.jit(_record_gram(mesh, cfg.use_kernel))
    key = jax.random.PRNGKey(seed + 1)
    w = jnp.ones((d,), f.dtype) / d
    ledger = Ledger.empty()
    rec_obs = cfg.obs is not None and ("eta" in cfg.obs.taps
                                       or "s" in cfg.obs.taps)
    tap_rows = []

    def record(params, f, w):
        hist["train_mse"].append(float(jnp.mean((y - w @ f) ** 2)))
        if xcols_test is not None:
            preds = jax.vmap(family.predict)(params, xcols_test)
            hist["test_mse"].append(float(jnp.mean((y_test - w @ preds) ** 2)))
        # same definition as core.icoa.run: eta of the optimally-weighted
        # ensemble on the FULL residual covariance (diagnostic, not traffic)
        a0r = record_gram(y[None, :] - f)
        hist["eta"].append(float(ensemble.eta(a0r)))
        if rec_obs:
            return obs_taps.record_taps(cfg.obs, ensemble.eta(a0r),
                                        ensemble.solve_vec(a0r))
        return {}

    record(params, f, w)
    eta_prev = float("inf")   # same rule as core.icoa.run: compare post-sweep etas
    for r in range(cfg.n_sweeps):
        key, k1 = jax.random.split(key)
        f, params, w, led2, etaps = sweep_fn(xcols, y, f, params, k1, ledger,
                                             jnp.asarray(r, jnp.int32))
        hist["bytes"].append(float(led2.spent - ledger.spent))
        ledger = led2
        rtaps = record(params, f, w)
        if cfg.obs is not None and cfg.obs.enabled:
            tap_rows.append({**etaps, **rtaps})
        eta_now = hist["eta"][-1]
        if abs(eta_prev - eta_now) < cfg.eps:
            break
        eta_prev = eta_now
    hist["taps"] = obs_taps.stack_tap_rows(tap_rows)
    return params, w, hist


def run_scan_distributed(family, cfg: ICOAConfig, xcols: jnp.ndarray,
                         y: jnp.ndarray, xcols_test: jnp.ndarray,
                         y_test: jnp.ndarray, seed, mesh: Mesh):
    """Fully-traceable distributed ICOA run: the shard_map Monte-Carlo block.

    Same math and key discipline as `run_distributed` — init from
    PRNGKey(seed), record with uniform weights, then per sweep
    `key, k1 = split(key)` and record with the sweep's returned weights — but
    the outer loop is a static `lax.scan` over cfg.n_sweeps whose body calls
    the shard_map'd sweep (collectives stage fine under scan), and every
    recorded quantity stays a jnp array.  `seed` may be a traced integer, so
    an enclosing `lax.scan` over trial indices executes a whole Monte-Carlo
    batch as ONE compiled program while each trial still runs
    one-agent-per-device (api.batch_fit's shard_map batch path, DESIGN.md §7).

    Returns (params, f, weights, hist): hist arrays of length n_sweeps + 1
    plus hist["converged_at"], where `run_distributed`'s eps rule would have
    stopped.
    """
    from repro.core import icoa as icoa_mod   # lazy: icoa imports nothing here

    d = xcols.shape[0]
    seed = jnp.asarray(seed)
    keys = jax.random.split(jax.random.PRNGKey(seed), d)
    params = jax.vmap(lambda k, x: family.fit(family.init(k), x, y))(keys, xcols)
    f = jax.vmap(family.predict)(params, xcols)

    sweep_fn = _sweep_shmap(mesh, cfg, family)
    record_gram = _record_gram(mesh, cfg.use_kernel)
    rec_obs = cfg.obs is not None and ("eta" in cfg.obs.taps
                                       or "s" in cfg.obs.taps)

    def record(params, f, w):
        train = jnp.mean((y - w @ f) ** 2)
        preds = jax.vmap(family.predict)(params, xcols_test)
        test = jnp.mean((y_test - w @ preds) ** 2)
        a0r = record_gram(y[None, :] - f)
        eta = ensemble.eta(a0r)
        rtaps = (obs_taps.record_taps(cfg.obs, eta, ensemble.solve_vec(a0r))
                 if rec_obs else {})
        return train, test, eta, rtaps

    w0 = jnp.ones((d,), f.dtype) / d
    tr0, te0, et0, _ = record(params, f, w0)
    key0 = jax.random.PRNGKey(seed + 1)

    def step(carry, r):
        params, f, key, led = carry
        key, k1 = jax.random.split(key)
        f, params, w, led2, etaps = sweep_fn(xcols, y, f, params, k1, led, r)
        tr, te, et, rtaps = record(params, f, w)
        return (params, f, key, led2), (w, tr, te, et,
                                        led2.spent - led.spent,
                                        {**etaps, **rtaps})

    (params, f, _, _), (ws, trs, tes, ets, bts, taps) = jax.lax.scan(
        step, (params, f, key0, Ledger.empty()),
        jnp.arange(cfg.n_sweeps))
    hist = {
        "train_mse": jnp.concatenate([tr0[None], trs]),
        "test_mse": jnp.concatenate([te0[None], tes]),
        "eta": jnp.concatenate([et0[None], ets]),
        "bytes": jnp.concatenate([jnp.zeros_like(bts[:1]), bts]),
    }
    hist["converged_at"] = icoa_mod.converged_record(hist["eta"], cfg.eps)
    hist["taps"] = taps
    return params, f, ws[-1], hist


# --------------------------------------------------------------------------
# The paper's comparison algorithms as collective schedules, so the api layer
# can run every solver on either backend. Both keep the attribute-sharding
# guarantee: xcols stays on its agent's device, only predictions move.


def _averaging_shmap(mesh: Mesh, family):
    """shard_map'd per-agent fit (traceable; no jit wrapper)."""

    def body(xcol, y, key):
        p = family.fit(family.init(key[0]), xcol[0], y)
        f = family.predict(p, xcol[0])
        return jax.tree.map(lambda t: t[None], p), f[None]

    return jax.shard_map(
        body, mesh=mesh, check_vma=False,
        in_specs=(P("agents"), P(), P("agents")),
        out_specs=(P("agents"), P("agents")),
    )


def run_averaging_distributed(family, xcols: jnp.ndarray, y: jnp.ndarray,
                              mesh: Optional[Mesh] = None, seed: int = 0):
    """Non-cooperative averaging under shard_map: every agent fits y on its own
    device; no inter-agent traffic at all (the paper's O(1) row of Fig. 2).
    Returns (params, f) with the same stacked layout as the local path."""
    d = xcols.shape[0]
    mesh = mesh or make_agent_mesh(d)
    keys = jax.random.split(jax.random.PRNGKey(seed), d)
    return jax.jit(_averaging_shmap(mesh, family))(xcols, y, keys)


def run_averaging_scan_distributed(family, xcols: jnp.ndarray, y: jnp.ndarray,
                                   xcols_test: jnp.ndarray,
                                   y_test: jnp.ndarray, seed, mesh: Mesh):
    """Traceable distributed averaging (seed may be traced): mirrors
    baselines.averaging_scan's (params, f, hist) contract — uniform-mean
    train/test MSE plus the eta diagnostic — with the per-agent fits running
    one-per-device."""
    d = xcols.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(jnp.asarray(seed)), d)
    params, f = _averaging_shmap(mesh, family)(xcols, y, keys)
    train = jnp.mean((y - f.mean(axis=0)) ** 2)
    ft = jax.vmap(family.predict)(params, xcols_test)
    test = jnp.mean((y_test - ft.mean(axis=0)) ** 2)
    eta = ensemble.eta(cov.gram(y[None, :] - f))
    hist = {"train_mse": train[None], "test_mse": test[None], "eta": eta[None]}
    return params, f, hist


def _refit_cycle_shmap(mesh: Mesh, family, codec=None):
    """shard_map'd ICEA ring cycle (traceable; no jit wrapper).  `codec`
    (transport.Codec) codes the delivered leave-me-out sum, exactly as the
    serial/scan variants do (baselines._loo_residual)."""

    def cycle(xcol, y, f_local, params_local):
        dd = jax.lax.psum(1, "agents")
        me = jax.lax.axis_index("agents")

        def agent_update(i, carry):
            f_local, params_local = carry
            f_sum = jax.lax.psum(f_local[0], "agents")                # (N,)
            residual = baselines._loo_residual(codec, y, f_sum, f_local[0])
            new_p = family.fit(jax.tree.map(lambda t: t[0], params_local),
                               xcol[0], residual)
            new_f = family.predict(new_p, xcol[0])
            is_me = (me == i)
            params_local = jax.tree.map(
                lambda old, new: jnp.where(is_me, new[None], old), params_local, new_p)
            f_local = jnp.where(is_me, new_f[None], f_local)
            return f_local, params_local

        return jax.lax.fori_loop(0, dd, agent_update, (f_local, params_local))

    return jax.shard_map(
        cycle, mesh=mesh, check_vma=False,
        in_specs=(P("agents"), P(), P("agents"), P("agents")),
        out_specs=(P("agents"), P("agents")),
    )


def run_refit_distributed(family, xcols: jnp.ndarray, y: jnp.ndarray,
                          xcols_test: Optional[jnp.ndarray] = None,
                          y_test: Optional[jnp.ndarray] = None,
                          n_cycles: int = 30, mesh: Optional[Mesh] = None,
                          seed: int = 0, codec=None):
    """Residual refitting (ICEA ring) under shard_map: one cycle = one
    round-robin pass; the updating agent needs only the ensemble SUM, so each
    update is a single psum of one (N,) vector — O(N*D) wire bytes per cycle,
    the ring cost of Fig. 2 and exactly what the api layer's byte accounting
    charges. Mirrors baselines.residual_refitting's (params, f, hist) return
    contract (params stacked over agents; ensemble prediction = sum of f)."""
    d = xcols.shape[0]
    mesh = mesh or make_agent_mesh(d)
    keys = jax.random.split(jax.random.PRNGKey(seed), d)

    cycle_fn = jax.jit(_refit_cycle_shmap(mesh, family, codec))

    params = baselines.align_param_dtypes(
        family, jax.vmap(lambda k: family.init(k))(keys), xcols[0], y)
    f = jnp.zeros((d, y.shape[0]), dtype=y.dtype)
    hist = {"train_mse": [], "test_mse": [], "eta": []}
    for _ in range(n_cycles):
        f, params = cycle_fn(xcols, y, f, params)
        hist["train_mse"].append(float(jnp.mean((y - f.sum(axis=0)) ** 2)))
        if xcols_test is not None:
            ft = jax.vmap(family.predict)(params, xcols_test)
            hist["test_mse"].append(float(jnp.mean((y_test - ft.sum(axis=0)) ** 2)))
        hist["eta"].append(float(ensemble.eta(cov.gram(y[None, :] - f))))
    return params, f, hist


def run_refit_scan_distributed(family, xcols: jnp.ndarray, y: jnp.ndarray,
                               xcols_test: jnp.ndarray, y_test: jnp.ndarray,
                               n_cycles: int, seed, mesh: Mesh, codec=None):
    """Traceable distributed residual refitting (seed may be traced): the ring
    cycles as a `lax.scan` whose body is the shard_map'd cycle — identical
    update order and leave-me-out residuals as `run_refit_distributed`, with
    per-cycle records kept as jnp arrays (no init record, matching the serial
    history contract)."""
    d = xcols.shape[0]
    keys = jax.random.split(jax.random.PRNGKey(jnp.asarray(seed)), d)
    cycle_fn = _refit_cycle_shmap(mesh, family, codec)

    params = baselines.align_param_dtypes(
        family, jax.vmap(family.init)(keys), xcols[0], y)
    f = jnp.zeros((d, y.shape[0]), dtype=y.dtype)

    def cycle(carry, _):
        params, f = carry
        f, params = cycle_fn(xcols, y, f, params)
        train = jnp.mean((y - f.sum(axis=0)) ** 2)
        ft = jax.vmap(family.predict)(params, xcols_test)
        test = jnp.mean((y_test - ft.sum(axis=0)) ** 2)
        eta = ensemble.eta(cov.gram(y[None, :] - f))
        return (params, f), (train, test, eta)

    (params, f), (trs, tes, ets) = jax.lax.scan(
        cycle, (params, f), None, length=n_cycles)
    hist = {"train_mse": trs, "test_mse": tes, "eta": ets}
    return params, f, hist
