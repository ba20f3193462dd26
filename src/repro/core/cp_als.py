"""CP-ALS (paper Algorithm 1) on top of any MTTKRP backend.

The MTTKRP backend is either a bare callable ``(factors, mode) -> M`` or any
object exposing ``.mttkrp(factors, mode)`` — in particular an
``repro.engine.ExecutionPlan`` (the unified engine API), but also the legacy
``DeviceBLCO`` / ``OOMExecutor`` wrappers.  ``as_mttkrp_fn`` is the adapter;
every driver below resolves its backend through it, so the same algorithm
runs over BLCO (in-memory or streaming/OOM), COO, F-COO, CSF, sharded, or
the Pallas kernel path — mirroring how the paper swaps formats under one
algorithm.

The algorithm is exposed at two granularities:

* ``cp_als`` — the one-shot driver (runs to convergence / iteration cap);
* ``cp_als_init`` + ``cp_als_step`` — a resumable stepper over an explicit
  ``CPState``, one full ALS sweep (all modes + fit update) per call. The
  multi-tenant service scheduler interleaves *iterations* of many jobs
  through this interface; ``cp_als`` is literally a loop over it, so both
  paths are numerically identical.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs import trace as obs_trace


@dataclasses.dataclass
class CPResult:
    factors: list        # N arrays (I_n, R), unit-norm columns
    lam: np.ndarray      # (R,) column weights
    fits: list           # per-iteration fit
    converged: bool
    iterations: int


@dataclasses.dataclass
class CPState:
    """Resumable CP-ALS state: everything one ALS sweep reads and writes."""
    dims: tuple
    rank: int
    norm_x: float
    tol: float
    factors: list        # N arrays (I_n, R), unit-norm columns
    lam: jnp.ndarray     # (R,) column weights
    grams: list          # N arrays (R, R) = factors[n].T @ factors[n]
    fits: list           # per-iteration fit, appended by each step
    prev_fit: float
    iteration: int       # completed ALS sweeps
    converged: bool

    def as_result(self) -> CPResult:
        return CPResult(factors=self.factors, lam=np.asarray(self.lam),
                        fits=self.fits, converged=self.converged,
                        iterations=self.iteration)


def as_mttkrp_fn(backend):
    """Adapt an engine/plan-or-callable MTTKRP backend to ``(factors, mode)``.

    Accepts (in priority order) any object with an ``mttkrp(factors, mode)``
    method — an ``ExecutionPlan``, ``DeviceBLCO``, ``OOMExecutor``, baseline
    device format — or a bare callable.  Bare callables pass through
    untouched, keeping the original ``cp_als(lambda f, m: ...)`` form intact.
    """
    method = getattr(backend, "mttkrp", None)
    if method is not None and callable(method):
        return method
    if callable(backend):
        return backend
    raise TypeError(
        f"MTTKRP backend must be a callable (factors, mode) -> M or expose "
        f".mttkrp(factors, mode); got {type(backend).__name__}")


def init_factors(dims, rank, *, seed=0, dtype=jnp.float32):
    rng = np.random.default_rng(seed)
    return [jnp.asarray(rng.standard_normal((d, rank)), dtype=dtype) for d in dims]


def _grams(factors):
    return [f.T @ f for f in factors]


def cp_als_init(dims, rank, *, norm_x: float, tol: float = 1e-5,
                seed: int = 0, dtype=jnp.float32, factors=None) -> CPState:
    """Fresh CP-ALS state (factors drawn from ``seed`` unless given)."""
    factors = list(factors) if factors is not None else \
        init_factors(dims, rank, seed=seed, dtype=dtype)
    return CPState(dims=tuple(dims), rank=rank, norm_x=norm_x, tol=tol,
                   factors=factors, lam=jnp.ones((rank,), dtype),
                   grams=_grams(factors), fits=[], prev_fit=-np.inf,
                   iteration=0, converged=False)


@jax.jit
def gram_solve(m_mat, v):
    """``m_mat @ pinv(v)`` for the symmetric positive semi-definite ``v``:
    a Cholesky solve, and the pseudo-inverse where ``v`` is singular to
    ``pinv``'s own tolerance (a Cholesky pivot under it, or none).

    The solve is backward stable.  The TPU's float32 SVD behind ``pinv``
    is not: as ALS turns ``v`` ill-conditioned its update drifts from a
    float64 reference by 4.9e-4 at condition 163, the solve's by 1.4e-5
    (PERF.md, TPU v5e).
    """
    chol, lower = jax.scipy.linalg.cho_factor(v)
    rtol = 10 * v.shape[0] * jnp.finfo(v.dtype).eps     # pinv's default
    # a NaN pivot (no factor) compares False and takes the pseudo-inverse
    ok = jnp.all(jnp.diagonal(chol) ** 2 > rtol * jnp.max(jnp.diagonal(v)))
    return jax.lax.cond(
        ok, lambda: jax.scipy.linalg.cho_solve((chol, lower), m_mat.T).T,
        lambda: m_mat @ jnp.linalg.pinv(v))


def sweep_mode_update(m_mat, grams, mode: int):
    """Pure device math of one ALS mode update (Alg. 1 lines 3 + 5).

    ``m_mat`` is the mode's MTTKRP result (line 4), ``grams`` the current
    Gram matrices.  Returns ``(factor, lam, gram)``: the column-normalized
    new factor, its column norms, and its refreshed Gram matrix.  Kept as a
    free jnp-pure function so the trace-tier jaxpr auditor
    (``repro.analysis.trace``) can audit the sweep body exactly as the
    scheduler executes it.
    """
    rank = m_mat.shape[1]
    dtype = grams[mode].dtype
    # V = hadamard of Gram matrices of all other modes (Alg. 1 line 3)
    v = jnp.ones((rank, rank), dtype)
    for m, g in enumerate(grams):
        if m != mode:
            v = v * g
    a_new = gram_solve(m_mat, v)                          # line 5
    lam = jnp.linalg.norm(a_new, axis=0)
    lam = jnp.where(lam > 0, lam, 1.0)
    factor = a_new / lam
    return factor, lam, factor.T @ factor


def cp_als_step(mttkrp_fn, state: CPState) -> CPState:
    """One full ALS sweep (all modes, Alg. 1 lines 2-6) + fit update, in place.

    ``mttkrp_fn`` is an engine plan, any ``.mttkrp``-bearing backend, or a
    bare callable returning the (I_mode, R) MTTKRP result (``as_mttkrp_fn``).
    Returns ``state`` for chaining; a converged state is returned unchanged.

    Spans (``repro.obs.trace``): ``als.sweep`` over the sweep, with
    ``als.mttkrp`` and ``als.update`` per mode and ``als.fit`` over the
    fit's device ops and its read-back.
    """
    if state.converged:
        return state
    mttkrp_fn = as_mttkrp_fn(mttkrp_fn)
    with obs_trace.span("als.sweep", "als", iteration=state.iteration):
        fit = _sweep(mttkrp_fn, state)
    state.fits.append(fit)
    state.iteration += 1
    if abs(fit - state.prev_fit) < state.tol:
        state.converged = True
    state.prev_fit = fit
    return state


def _sweep(mttkrp_fn, state: CPState) -> float:
    """Every mode's update of ``state`` in place; the fit it reaches."""
    n_modes = len(state.dims)
    rank = state.rank
    dtype = state.factors[0].dtype
    factors, grams = state.factors, state.grams
    m_mat = None
    for n in range(n_modes):
        with obs_trace.span("als.mttkrp", "als", mode=n):
            m_mat = mttkrp_fn(factors, n)                # line 4
        with obs_trace.span("als.update", "als", mode=n):
            factors[n], lam, grams[n] = sweep_mode_update(m_mat, grams, n)
            state.lam = lam

    # fit = 1 - ||X - X_hat||_F / ||X||_F, computed without materializing
    # X_hat (standard CP-ALS identity; m_mat is the last mode's MTTKRP).
    with obs_trace.span("als.fit", "als"):
        last = n_modes - 1
        v_all = jnp.ones((rank, rank), dtype)
        for m in range(n_modes):
            v_all = v_all * grams[m]
        norm_est_sq = state.lam @ (v_all @ state.lam)
        inner = jnp.sum(state.lam * jnp.sum(m_mat * factors[last], axis=0))
        resid_sq = jnp.maximum(state.norm_x ** 2 + norm_est_sq
                               - 2.0 * inner, 0.0)
        return float(1.0 - jnp.sqrt(resid_sq) / state.norm_x)


def cp_als(mttkrp_fn, dims, rank, *, norm_x: float, iters: int = 25,
           tol: float = 1e-5, seed: int = 0, dtype=jnp.float32,
           factors=None) -> CPResult:
    """Alternating least squares for rank-R CPD (one-shot driver).

    ``mttkrp_fn``: an engine plan / ``.mttkrp``-bearing backend or a bare
    callable (factors, mode) -> (I_mode, R) — see ``as_mttkrp_fn``.
    norm_x: Frobenius norm of the sparse tensor (sum of squared values)**0.5.
    """
    mttkrp_fn = as_mttkrp_fn(mttkrp_fn)
    state = cp_als_init(dims, rank, norm_x=norm_x, tol=tol, seed=seed,
                        dtype=dtype, factors=factors)
    for _ in range(iters):
        cp_als_step(mttkrp_fn, state)
        if state.converged:
            break
    return state.as_result()


def reconstruct_dense(result: CPResult) -> np.ndarray:
    """Dense reconstruction from factors (test oracle; small tensors only)."""
    factors = [np.asarray(f, np.float64) for f in result.factors]
    lam = np.asarray(result.lam, np.float64)
    rank = lam.shape[0]
    dims = [f.shape[0] for f in factors]
    out = np.zeros(dims)
    for r in range(rank):
        term = lam[r]
        vecs = [f[:, r] for f in factors]
        acc = vecs[0]
        for v in vecs[1:]:
            acc = np.multiply.outer(acc, v)
        out += term * acc
    return out
