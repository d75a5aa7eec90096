"""TRON: Trust-Region Newton method with Steihaug-CG inner solves.

Faithful JAX port of the solver the paper uses (Lin, Weng & Keerthi,
"Trust region Newton methods for large-scale logistic regression", ICML'07
— reference [16]; the liblinear tron.cpp update rules). Fully jittable:
outer iteration and inner CG are ``lax.while_loop``s, so the whole solve —
including the distributed f/g/Hd closures with their psum AllReduces —
lowers to a single XLA program. This is the TPU answer to the paper's §4.4
latency pathology: 5N AllReduce calls become on-device ICI collectives
inside one compiled loop, with zero per-call host latency.

The solver is generic over two closures:
    fgrad(beta)  -> (f, g, aux)   # aux = Gauss-Newton diagonal info
    hessd(aux, d) -> H d
so the same code runs the local, the shard_map-distributed, and the
materialization-free (fused Pallas) problem variants.

Both drivers are additionally generic over a trailing *column* axis:
``beta0`` may be the classic (m,) vector or an (m, K) block of K
independent problems (one-vs-rest multiclass — each column has its own y
and therefore its own objective). Every scalar of the update rules (f,
delta, gnorm, the CG dots) becomes a (K,)-vector, every branch a
per-column mask, and the loop runs until all columns converge. The payoff
is that each f/g/Hd closure call evaluates ALL columns at once: with the
fused kmvp closures one gram recomputation pass serves K columns instead
of K separate solves paying K passes. Columns that converge early are
frozen by masks (their CG direction is zeroed), so lockstep iteration
never changes any column's trajectory versus a solo run of that column.

Two drivers share the update rules:
  * :func:`tron` — fully traced (``lax.while_loop``); closures must be
    jax-traceable. Every in-memory plan uses this.
  * :func:`tron_host` — the same algorithm as an eager host loop, for
    closures that cannot be traced because each f/g/Hd evaluation is an
    *accumulation over data chunks streamed from disk* (the ``stream``
    execution plan). The m-vector CG algebra runs in numpy on the host;
    all O(n) work stays inside the chunk closures.

Both drivers are resumable: the complete iterate state of either loop is
the O(m·K) :class:`TronSnapshot` — beta, the per-column trust radii,
``gnorm0`` (the convergence reference), the per-column live masks, and
the three counters. Everything else the loops carry (f, g, aux) is a pure
deterministic function of beta: after a *rejected* step the retained
f/g/aux still correspond to the retained beta, so one ``fgrad(beta)``
call on restore rebuilds them and a resumed solve walks the exact
trajectory of the uninterrupted *checkpointed* run — bit-identically,
because the traced driver re-derives f/g/aux from beta inside the same
jitted segment program at every snapshot boundary (see :func:`tron`) and
the host driver's eager ``fgrad`` is deterministic call-for-call.
``snapshot_every`` / ``on_snapshot`` emit snapshots periodically (the
traced driver runs the ``lax.while_loop`` in jitted segments of that
many iterations so the host can observe the state between them; with
both unset the original single-while_loop program is unchanged), and
``state0`` restores one.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class TronConfig:
    max_iter: int = 200          # outer Newton iterations (paper: N ~ 300)
    grad_rtol: float = 1e-3      # stop when ||g|| <= grad_rtol * ||g0||
    cg_rtol: float = 0.1         # inner CG: ||r|| <= cg_rtol * ||g||
    cg_max_iter: int = 64        # cap on CG steps per outer iteration
    eta0: float = 1e-4           # step acceptance threshold
    eta1: float = 0.25
    eta2: float = 0.75
    sigma1: float = 0.25         # trust-region shrink/grow factors
    sigma2: float = 0.5
    sigma3: float = 4.0


class TronResult(NamedTuple):
    beta: jnp.ndarray     # (m,) — or (m, K) for a column-batched solve
    f: jnp.ndarray        # scalar — or (K,) per-column objectives
    gnorm: jnp.ndarray    # scalar — or (K,)
    n_iter: jnp.ndarray   # outer iterations performed (shared loop trips)
    n_fg: jnp.ndarray     # function/gradient evaluations (paper step 4a/4b calls)
    n_hd: jnp.ndarray     # Hessian-vector products     (paper step 4c calls)
    converged: jnp.ndarray  # scalar bool — or (K,) per column
    f_hist: jnp.ndarray   # (max_iter + 1[, K]) objective after each outer
    #                       iteration (entry 0: at beta0); NaN past n_iter
    #                       and before a resumed start


class TronSnapshot(NamedTuple):
    """Resumable iterate state of a TRON solve, as host numpy arrays.

    Deliberately minimal — O(m·K) floats plus four scalars. f, g and aux
    are NOT stored: they are pure deterministic functions of ``beta``
    (even after a rejected step the retained f/g/aux correspond to the
    retained beta), so restore re-evaluates ``fgrad(beta)`` once and gets
    them back bit-identically. That re-evaluation is NOT counted in
    ``n_fg``, so a resumed run's counters match the uninterrupted run's.
    """
    beta: np.ndarray      # (m[, K]) iterate
    delta: np.ndarray     # trust radius — scalar or (K,)
    gnorm0: np.ndarray    # ||g(beta_0)|| convergence reference
    active: np.ndarray    # per-column live mask (stagnation-guard state)
    it: int               # outer iterations completed
    n_fg: int
    n_hd: int

    def to_arrays(self) -> dict:
        """Flat name->array dict, ready for an .npz checkpoint."""
        return {
            "beta": np.asarray(self.beta),
            "delta": np.asarray(self.delta),
            "gnorm0": np.asarray(self.gnorm0),
            "active": np.asarray(self.active),
            "it": np.asarray(int(self.it), np.int64),
            "n_fg": np.asarray(int(self.n_fg), np.int64),
            "n_hd": np.asarray(int(self.n_hd), np.int64),
        }

    @classmethod
    def from_arrays(cls, arrays: dict) -> "TronSnapshot":
        return cls(beta=np.asarray(arrays["beta"]),
                   delta=np.asarray(arrays["delta"]),
                   gnorm0=np.asarray(arrays["gnorm0"]),
                   active=np.asarray(arrays["active"], bool),
                   it=int(arrays["it"]),
                   n_fg=int(arrays["n_fg"]),
                   n_hd=int(arrays["n_hd"]))


def _cdot(a, b):
    """Per-column dot: a scalar for (m,) operands, (K,) for (m, K).

    The 1-D case keeps the exact dot/norm primitives of the single-RHS
    solver so its f32 rounding (and therefore its tested convergence
    trajectories) is unchanged by the column-batched generalization.
    """
    if a.ndim == 1:
        return a @ b
    return jnp.sum(a * b, axis=0)


def _cnorm(a):
    if a.ndim == 1:
        return jnp.linalg.norm(a)
    return jnp.sqrt(jnp.sum(a * a, axis=0))


class _CGState(NamedTuple):
    s: jnp.ndarray
    r: jnp.ndarray
    d: jnp.ndarray
    rtr: jnp.ndarray
    it: jnp.ndarray
    active: jnp.ndarray


def _steihaug_cg(g, hvp: Callable, delta, tol, max_iter: int, active0=None):
    """Steihaug-Toint CG: approximately minimize g.s + 0.5 s'Hs, ||s||<=delta.

    Returns (s, r, n_hd) with r = -g - H s maintained through boundary exits
    (liblinear trcg semantics) so the caller can form the predicted
    reduction as -0.5*(g.s - s.r).

    Column-batched when g is (m, K): delta/tol are (K,), every iteration
    makes ONE hvp call on the whole (m, K) direction block (the fused-kmvp
    amortization), and columns that hit the boundary or their tolerance are
    frozen (their direction zeroed) while the rest keep iterating.
    ``active0`` masks out columns the outer loop already finished.
    """
    multi = g.ndim > 1
    # In the classic 1-D problem every mask below is trace-time True while
    # the loop runs, so the masking selects are elided entirely — the
    # lowered 1-D program (and its f32 rounding) is unchanged from the
    # single-RHS solver.
    sel = (lambda run, new, old: jnp.where(run, new, old)) if multi \
        else (lambda run, new, old: new)
    zero = jnp.zeros_like(g)
    init = _CGState(
        s=zero, r=-g, d=-g,
        rtr=_cdot(g, g),
        it=jnp.array(0, jnp.int32),
        active=(jnp.ones(g.shape[1:], bool) if active0 is None else active0)
        if multi else jnp.asarray(True if active0 is None else active0),
    )

    def cond(st: _CGState):
        live = st.active & (jnp.sqrt(st.rtr) > tol)
        return (jnp.any(live) if multi else live) & (st.it < max_iter)

    def body(st: _CGState):
        run = st.active & (jnp.sqrt(st.rtr) > tol)
        d_run = sel(run, st.d, jnp.zeros_like(st.d))  # frozen cols: no motion
        Hd = hvp(d_run)
        dHd = _cdot(d_run, Hd)
        # Negative curvature or step leaving the region -> go to boundary.
        alpha = st.rtr / jnp.where(dHd > 0, dHd, 1.0)
        s_try = st.s + alpha * d_run
        outside = (_cnorm(s_try) >= delta) | (dHd <= 0)

        # tau >= 0 solving ||s + tau d|| = delta
        sd = _cdot(st.s, d_run)
        dd = _cdot(d_run, d_run)
        ss = _cdot(st.s, st.s)
        rad = jnp.sqrt(jnp.maximum(sd * sd + dd * (delta * delta - ss), 0.0))
        tau = (rad - sd) / jnp.where(dd > 0, dd, 1.0)

        step = jnp.where(outside, tau, alpha)
        s_new = sel(run, st.s + step * d_run, st.s)
        r_new = sel(run, st.r - step * Hd, st.r)
        rtr_new = _cdot(r_new, r_new)
        beta_cg = rtr_new / jnp.where(st.rtr > 0, st.rtr, 1.0)
        d_new = sel(run, r_new + beta_cg * st.d, st.d)
        return _CGState(
            s=s_new, r=r_new, d=d_new, rtr=rtr_new,
            it=st.it + 1,
            active=st.active & ~(run & outside) if multi else ~outside,
        )

    final = jax.lax.while_loop(cond, body, init)
    return final.s, final.r, final.it


class _TronState(NamedTuple):
    beta: jnp.ndarray
    f: jnp.ndarray
    g: jnp.ndarray
    aux: jnp.ndarray
    delta: jnp.ndarray
    it: jnp.ndarray
    n_fg: jnp.ndarray
    n_hd: jnp.ndarray
    gnorm0: jnp.ndarray
    active: jnp.ndarray
    fs: jnp.ndarray       # f history, see TronResult.f_hist


def _f_history(f, cfg: TronConfig):
    """NaN-filled (max_iter + 1[, K]) history buffer for objectives like f."""
    return jnp.full((cfg.max_iter + 1,) + jnp.shape(f), jnp.nan,
                    jnp.result_type(f))


def snapshot_of(st) -> TronSnapshot:
    """Host :class:`TronSnapshot` of a live loop state (traced or host)."""
    return TronSnapshot(beta=np.asarray(st.beta), delta=np.asarray(st.delta),
                        gnorm0=np.asarray(st.gnorm0),
                        active=np.asarray(st.active, bool),
                        it=int(st.it), n_fg=int(st.n_fg), n_hd=int(st.n_hd))


def tron(fgrad: Callable, hessd: Callable, beta0: jnp.ndarray,
         cfg: TronConfig = TronConfig(), *,
         state0: TronSnapshot | None = None,
         snapshot_every: int = 0,
         on_snapshot: Callable[[TronSnapshot], None] | None = None
         ) -> TronResult:
    """Minimize f via trust-region Newton-CG. See module docstring.

    ``beta0`` (m,) runs the classic solver; (m, K) runs K independent
    problems in lockstep — one fgrad/hessd call per iteration serves every
    column, each column keeping its own f, trust radius, and convergence.

    ``state0`` resumes from a :class:`TronSnapshot` (beta0 then only fixes
    dtype/shape). ``snapshot_every`` > 0 runs the loop in jitted segments
    of that many outer iterations, calling ``on_snapshot`` with the live
    state between segments — the update rules are identical, only the
    while_loop trip grouping changes. With all three unset the original
    single-``lax.while_loop`` program is emitted unchanged.
    """
    multi = jnp.ndim(beta0) > 1
    sel = (lambda run, new, old: jnp.where(run, new, old)) if multi \
        else (lambda run, new, old: new)

    def cond(st: _TronState):
        live = st.active & (_cnorm(st.g) > cfg.grad_rtol * st.gnorm0)
        return (jnp.any(live) if multi else live) & (st.it < cfg.max_iter)

    def body(st: _TronState):
        gnorm = _cnorm(st.g)
        run = st.active & (gnorm > cfg.grad_rtol * st.gnorm0)
        hvp = lambda d: hessd(st.aux, d)
        s, r, cg_steps = _steihaug_cg(
            st.g, hvp, st.delta, cfg.cg_rtol * gnorm, cfg.cg_max_iter,
            active0=run if multi else None)

        snorm = _cnorm(s)
        gs = _cdot(st.g, s)
        prered = -0.5 * (gs - _cdot(s, r))

        beta_try = st.beta + s          # finished columns have s = 0
        f_new, g_new, aux_new = fgrad(beta_try)
        actred = st.f - f_new

        # liblinear delta-update rules
        denom = f_new - st.f - gs
        alpha = jnp.where(denom <= 0, cfg.sigma3,
                          jnp.maximum(cfg.sigma1, -0.5 * (gs / jnp.where(denom == 0, 1.0, denom))))
        # On the very first iteration, recalibrate delta to the step scale.
        delta = jnp.where(st.it == 0, jnp.minimum(st.delta, snorm), st.delta)
        delta = jnp.where(
            actred < cfg.eta0 * prered,
            jnp.minimum(jnp.maximum(alpha, cfg.sigma1) * snorm, cfg.sigma2 * delta),
            jnp.where(
                actred < cfg.eta1 * prered,
                jnp.maximum(cfg.sigma1 * delta, jnp.minimum(alpha * snorm, cfg.sigma2 * delta)),
                jnp.where(
                    actred < cfg.eta2 * prered,
                    jnp.maximum(cfg.sigma1 * delta, jnp.minimum(alpha * snorm, cfg.sigma3 * delta)),
                    jnp.maximum(delta, jnp.minimum(alpha * snorm, cfg.sigma3 * delta)),
                ),
            ),
        )
        delta = sel(run, delta, st.delta)

        accept = (actred > cfg.eta0 * prered) & run if multi \
            else actred > cfg.eta0 * prered
        beta = jnp.where(accept, beta_try, st.beta)
        f = jnp.where(accept, f_new, st.f)
        g = jnp.where(accept, g_new, st.g)
        aux = jax.tree.map(lambda a, b: jnp.where(accept, a, b), aux_new, st.aux)

        # Numerical stagnation guards (liblinear): stop on non-positive
        # predicted reduction or vanishing |actred|,|prered| relative to |f|.
        feps = jnp.abs(st.f) * 1e-12
        stagnated = (prered <= 0) | (
            (jnp.abs(actred) <= feps) & (jnp.abs(prered) <= feps))
        return _TronState(
            beta=beta, f=f, g=g, aux=aux, delta=delta,
            it=st.it + 1,
            n_fg=st.n_fg + 1,
            n_hd=st.n_hd + cg_steps,
            gnorm0=st.gnorm0,
            active=st.active & ~(run & stagnated) if multi
            else st.active & ~stagnated,
            fs=st.fs.at[st.it + 1].set(f),
        )

    if state0 is None and snapshot_every <= 0 and on_snapshot is None:
        f0, g0, aux0 = fgrad(beta0)
        gnorm0 = _cnorm(g0)
        init = _TronState(
            beta=beta0, f=f0, g=g0, aux=aux0,
            delta=gnorm0,
            it=jnp.array(0, jnp.int32),
            n_fg=jnp.array(1, jnp.int32),
            n_hd=jnp.array(0, jnp.int32),
            gnorm0=gnorm0,
            active=gnorm0 > 0,
            fs=_f_history(f0, cfg).at[0].set(f0),
        )
        st = jax.lax.while_loop(cond, body, init)     # the original program
    else:
        # Segmented driver: jit one while_loop whose cond adds a traced
        # iteration cap, run it `snapshot_every` iterations at a time, and
        # hand the host the live state between segments. Crucially the
        # canonical cross-segment state is exactly the TronSnapshot tuple:
        # f/g/aux are re-derived from beta INSIDE the jitted segment (not
        # carried over), so a run resumed from a stored snapshot replays
        # the identical compiled computation the uninterrupted
        # checkpointed run performs at that same boundary — bit-identical
        # trajectories. (A checkpointed run may therefore differ from an
        # un-checkpointed one at float-rounding level: the boundary
        # re-derivation re-rounds f/g/aux every `snapshot_every`
        # iterations. The re-derivations are not counted in n_fg.)
        @jax.jit
        def _segment(beta, delta, gnorm0, active, it, n_fg, n_hd, fs, cap):
            f, g, aux = fgrad(beta)
            st = _TronState(beta=beta, f=f, g=g, aux=aux, delta=delta,
                            it=it, n_fg=n_fg, n_hd=n_hd, gnorm0=gnorm0,
                            active=active, fs=fs.at[it].set(f))

            def seg_cond(s):
                return cond(s) & (s.it < cap)
            return jax.lax.while_loop(seg_cond, body, st)

        def _run_segment(st, cap: int):
            return _segment(st.beta, st.delta, st.gnorm0, st.active, st.it,
                            st.n_fg, st.n_hd, st.fs,
                            jnp.asarray(cap, jnp.int32))

        def _host_live(st):
            g = np.asarray(st.g, np.float64)
            gnorm_h = np.sqrt(np.sum(g * g, axis=0)) if multi \
                else np.linalg.norm(g)
            live = np.asarray(st.active) \
                & (gnorm_h > cfg.grad_rtol * np.asarray(st.gnorm0))
            return bool(np.any(live)) and int(st.it) < cfg.max_iter

        if state0 is None:
            f0, g0, aux0 = fgrad(beta0)        # counted: the fresh init eval
            gnorm0 = _cnorm(g0)
            st = _TronState(
                beta=beta0, f=f0, g=g0, aux=aux0,
                delta=gnorm0,
                it=jnp.array(0, jnp.int32),
                n_fg=jnp.array(1, jnp.int32),
                n_hd=jnp.array(0, jnp.int32),
                gnorm0=gnorm0,
                active=gnorm0 > 0,
                fs=_f_history(f0, cfg),
            )
        else:
            beta_r = jnp.asarray(np.asarray(state0.beta),
                                 jnp.asarray(beta0).dtype)
            rt = beta_r.dtype
            st0 = _TronState(
                beta=beta_r, f=None, g=None, aux=None,  # rebuilt in-segment
                delta=jnp.asarray(np.asarray(state0.delta), rt),
                it=jnp.array(int(state0.it), jnp.int32),
                n_fg=jnp.array(int(state0.n_fg), jnp.int32),
                n_hd=jnp.array(int(state0.n_hd), jnp.int32),
                gnorm0=jnp.asarray(np.asarray(state0.gnorm0), rt),
                active=jnp.asarray(np.asarray(state0.active, bool)) if multi
                else jnp.asarray(bool(state0.active)),
                fs=_f_history(jnp.zeros(np.shape(state0.gnorm0), rt), cfg),
            )
            # Zero-trip segment: rebuild f/g/aux from beta through the SAME
            # jitted program the loop uses, so even the between-segment
            # convergence decision sees the exact bits the uninterrupted
            # run saw at this boundary. Not counted in n_fg.
            st = _run_segment(st0, int(st0.it))

        every = snapshot_every if snapshot_every > 0 else cfg.max_iter
        while _host_live(st):
            cap = min(cfg.max_iter, int(st.it) + every)
            st = _run_segment(st, cap)
            if on_snapshot is not None and snapshot_every > 0:
                on_snapshot(snapshot_of(st))
    gnorm = _cnorm(st.g)
    return TronResult(
        beta=st.beta, f=st.f, gnorm=gnorm,
        n_iter=st.it, n_fg=st.n_fg, n_hd=st.n_hd,
        converged=gnorm <= cfg.grad_rtol * st.gnorm0,
        f_hist=st.fs,
    )


# --------------------------------------------------------------- host driver
def _cdot_np(a, b):
    return np.sum(a * b, axis=0)


def _cnorm_np(a):
    return np.sqrt(np.sum(a * a, axis=0))


def _steihaug_cg_host(g, hvp: Callable, delta, tol, max_iter: int,
                      active0=None):
    """Host mirror of :func:`_steihaug_cg`: same trcg semantics, numpy
    vectors, eager ``hvp`` calls (each one may stream the dataset).

    Column-batched like the traced version: (m, K) g runs K problems per
    hvp call with per-column freeze masks; (m,) reduces to the classic
    scalar loop (masks are 0-d and always true while the loop runs). All
    m-vector state and scalar algebra run in float64 on the host, matching
    the ``float()`` precision of the pre-batched implementation; only the
    hvp argument drops to the problem dtype.
    """
    dtype = g.dtype
    g = g.astype(np.float64)
    s = np.zeros_like(g)
    r = -g
    d = -g
    rtr = _cdot_np(g, g)
    active = np.ones(g.shape[1:], bool) if active0 is None \
        else np.asarray(active0) & np.ones(g.shape[1:], bool)
    it = 0
    while np.any(active & (np.sqrt(rtr) > tol)) and it < max_iter:
        run = active & (np.sqrt(rtr) > tol)
        d_run = np.where(run, d, 0.0)
        Hd = np.asarray(hvp(d_run.astype(dtype)), np.float64)
        dHd = _cdot_np(d_run, Hd)
        alpha = rtr / np.where(dHd > 0, dHd, 1.0)
        s_try = s + alpha * d_run
        outside = (_cnorm_np(s_try) >= delta) | (dHd <= 0)

        sd = _cdot_np(s, d_run)
        dd = _cdot_np(d_run, d_run)
        ss = _cdot_np(s, s)
        rad = np.sqrt(np.maximum(sd * sd + dd * (delta * delta - ss), 0.0))
        tau = (rad - sd) / np.where(dd > 0, dd, 1.0)

        step = np.where(outside, tau, alpha)
        s = np.where(run, s + step * d_run, s)
        r = np.where(run, r - step * Hd, r)
        rtr_new = _cdot_np(r, r)
        beta_cg = rtr_new / np.where(rtr > 0, rtr, 1.0)
        d = np.where(run, r + beta_cg * d, d)
        rtr = rtr_new
        active = active & ~(run & outside)
        it += 1
    return s, r, it


def tron_host(fgrad: Callable, hessd: Callable, beta0,
              cfg: TronConfig = TronConfig(), *,
              state0: TronSnapshot | None = None,
              snapshot_every: int = 0,
              on_snapshot: Callable[[TronSnapshot], None] | None = None
              ) -> TronResult:
    """Eager trust-region Newton-CG with the exact update rules of
    :func:`tron`, for accumulator-style closures.

    ``fgrad``/``hessd`` may be arbitrary Python callables — in the
    ``stream`` plan each call loops over dataset chunks, accumulating the
    m-vector on the host while per-chunk math runs jitted on the mesh.
    ``aux`` is treated as a pytree of per-column arrays (the stream plan
    keeps the Gauss-Newton diagonal as one row-sharded array per chunk).

    Column-batched like :func:`tron` when ``beta0`` is (m, K): every
    streamed fgrad/hessd pass over the dataset then serves all K columns.

    ``state0`` resumes from a :class:`TronSnapshot`; f/g/aux are rebuilt
    by one (uncounted) ``fgrad`` call, so a resumed solve walks the exact
    trajectory of the uninterrupted one. ``snapshot_every`` > 0 calls
    ``on_snapshot`` with the live state every that many outer iterations.
    """
    beta = np.asarray(beta0)
    dtype = beta.dtype
    cols = beta.shape[1:]
    if state0 is not None:
        beta = np.asarray(state0.beta, dtype)
    f, g, aux = fgrad(beta)
    f = np.asarray(f, np.float64)
    g = np.asarray(g, dtype)
    if state0 is None:
        gnorm0 = _cnorm_np(g.astype(np.float64))
        delta = np.asarray(gnorm0).copy()
        it, n_fg, n_hd = 0, 1, 0
        active = np.asarray(gnorm0 > 0) & np.ones(cols, bool)
    else:
        gnorm0 = np.asarray(state0.gnorm0, np.float64)
        delta = np.asarray(state0.delta, np.float64).copy()
        it, n_fg, n_hd = int(state0.it), int(state0.n_fg), int(state0.n_hd)
        active = np.asarray(state0.active, bool) & np.ones(cols, bool)
    fs = np.full((cfg.max_iter + 1,) + f.shape, np.nan)
    fs[it] = f
    while np.any(active & (_cnorm_np(g) > cfg.grad_rtol * gnorm0)) \
            and it < cfg.max_iter:
        gnorm = _cnorm_np(g.astype(np.float64))
        run = active & (gnorm > cfg.grad_rtol * gnorm0)
        s, r, cg_steps = _steihaug_cg_host(
            g, lambda d: hessd(aux, d), delta, cfg.cg_rtol * gnorm,
            cfg.cg_max_iter, active0=run)
        n_hd += cg_steps

        snorm = _cnorm_np(s.astype(np.float64))
        gs = _cdot_np(g.astype(np.float64), s)
        prered = -0.5 * (gs - _cdot_np(s.astype(np.float64), r))

        beta_try = (beta + s).astype(dtype)
        f_new, g_new, aux_new = fgrad(beta_try)
        f_new = np.asarray(f_new, np.float64)
        g_new = np.asarray(g_new, dtype)
        n_fg += 1
        actred = f - f_new

        denom = f_new - f - gs
        alpha = np.where(denom <= 0, cfg.sigma3,
                         np.maximum(cfg.sigma1,
                                    -0.5 * (gs / np.where(denom == 0, 1.0,
                                                          denom))))
        if it == 0:
            delta = np.minimum(delta, snorm)
        delta_new = np.where(
            actred < cfg.eta0 * prered,
            np.minimum(np.maximum(alpha, cfg.sigma1) * snorm,
                       cfg.sigma2 * delta),
            np.where(
                actred < cfg.eta1 * prered,
                np.maximum(cfg.sigma1 * delta,
                           np.minimum(alpha * snorm, cfg.sigma2 * delta)),
                np.where(
                    actred < cfg.eta2 * prered,
                    np.maximum(cfg.sigma1 * delta,
                               np.minimum(alpha * snorm, cfg.sigma3 * delta)),
                    np.maximum(delta,
                               np.minimum(alpha * snorm, cfg.sigma3 * delta)),
                ),
            ),
        )
        delta = np.where(run, delta_new, delta)

        accept = (actred > cfg.eta0 * prered) & run
        beta = np.where(accept, beta_try, beta).astype(dtype)
        f = np.where(accept, f_new, f)
        g = np.where(accept, g_new, g).astype(dtype)
        # jnp.where: stream aux chunks are sharded device arrays — merging
        # on host would drag them off-device and re-transfer every Hd call
        aux = jax.tree.map(lambda a, b: jnp.where(accept, a, b), aux_new, aux)
        it += 1
        fs[it] = f

        feps = np.abs(f) * 1e-12
        stagnated = (prered <= 0) | (
            (np.abs(actred) <= feps) & (np.abs(prered) <= feps))
        active = active & ~(run & stagnated)

        if on_snapshot is not None and snapshot_every > 0 \
                and it % snapshot_every == 0:
            on_snapshot(TronSnapshot(
                beta=beta.copy(), delta=np.asarray(delta).copy(),
                gnorm0=np.asarray(gnorm0).copy(),
                active=np.asarray(active, bool).copy(),
                it=it, n_fg=n_fg, n_hd=n_hd))

    gnorm = _cnorm_np(g.astype(np.float64))
    return TronResult(
        beta=jnp.asarray(beta, dtype),
        f=jnp.asarray(np.asarray(f), jnp.float32),
        gnorm=jnp.asarray(np.asarray(gnorm), jnp.float32),
        n_iter=jnp.asarray(it, jnp.int32),
        n_fg=jnp.asarray(n_fg, jnp.int32),
        n_hd=jnp.asarray(n_hd, jnp.int32),
        converged=jnp.asarray(np.asarray(gnorm <= cfg.grad_rtol * gnorm0)),
        f_hist=jnp.asarray(fs, jnp.float32),
    )
