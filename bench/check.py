"""Decide ``correct``: the timed path's own outputs against ``reference``.

During the window a ``Recorder`` stands between ``cp_als_step`` and the
plan.  It keeps, for every MTTKRP call, the factor arrays that entered it
and the result (device arrays, a few MB each), and for every sweep the
factors, weights and fit it ended with.  After the window, ``compare``
takes a sample drawn from the seed (the window's last sweep and one other,
every mode of each, every row) and reports three numbers, each the worst
over the sample:

* ``mttkrp_err``: max |M - M_ref| / max |M_ref| per call, on ``COLS``
  rank columns drawn from the seed, and max |M Q - (M Q)_ref| /
  max |(M Q)_ref| on ``DIRS`` Gaussian directions ``Q`` through every rank
  column, in the last sweep, so that a fault in any column shows;
* ``update_err``: max |A - A_ref| of the new unit-column factor, with
  ``A_ref`` computed from the program's own M of that call;
* ``fit_err``: |fit - fit_ref| of each sampled sweep, over the scale of
  the terms that cancel in it (``reference.fit``; about 1 at the cells'
  sizes).

A sweep that made other than one MTTKRP per mode, or a non-finite fit, is
a fault and makes the run incorrect whatever the numbers say.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

from bench import reference

COLS = 4            # rank columns compared per sampled call
DIRS = 4            # random directions through all rank columns, last sweep
SWEEPS = 2          # sweeps compared per run: the last and one drawn
NUMBERS = ("mttkrp_err", "update_err", "fit_err")


@dataclasses.dataclass
class Sweep:
    calls: list = dataclasses.field(default_factory=list)  # (factors, mode, M)
    factors: tuple = ()
    lam: object = None
    fit: float = float("nan")
    times: list = dataclasses.field(default_factory=list)  # host clock

    def phases(self) -> list[tuple[str, float]]:
        """Host seconds of each MTTKRP call and of what ran after it: the
        mode update, and after the last mode the update and the fit."""
        names = [f"{kind}{m}" for _, m, _ in self.calls
                 for kind in ("mttkrp", "update")]
        return [(n, b - a) for n, a, b in
                zip(names, self.times, self.times[1:])]


class Recorder:
    """``(factors, mode) -> M`` over ``plan``, keeping what it saw."""

    def __init__(self, plan, annotate):
        self.plan = plan
        self.annotate = annotate        # name -> context manager
        self.sweeps: list[Sweep] = []
        self._open = None

    def begin_sweep(self) -> None:
        self.sweeps.append(Sweep())

    def end_sweep(self, state) -> None:
        self._close_gap()
        s = self.sweeps[-1]
        s.times.append(time.perf_counter())
        s.factors = tuple(state.factors)
        s.lam = state.lam
        s.fit = state.fits[-1] if state.fits else float("nan")

    def _close_gap(self) -> None:
        if self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None

    def __call__(self, factors, mode: int):
        self._close_gap()
        sweep = self.sweeps[-1]
        sweep.times.append(time.perf_counter())
        with self.annotate(f"bench.mttkrp.mode{mode}"):
            out = self.plan.mttkrp(factors, mode)
        sweep.times.append(time.perf_counter())
        sweep.calls.append((tuple(factors), mode, out))
        # host time up to the next call is the mode update (and, after the
        # last mode, the update and the fit)
        last = mode == len(factors) - 1
        self._open = self.annotate("bench.update_fit" if last
                                   else "bench.update")
        self._open.__enter__()
        return out


def sample(n_sweeps: int, rng) -> list[int]:
    """Indices of the sweeps to compare: the last, and one other drawn."""
    if n_sweeps == 0:
        return []
    picks = {n_sweeps - 1}
    if n_sweeps > 1:
        picks.add(int(rng.integers(0, n_sweeps - 1)))
    return sorted(picks)[-SWEEPS:]


def _to_host(x):
    return np.asarray(x, np.float64)


def sweep_faults(sweep: Sweep, order: int) -> list[str]:
    modes = [m for _, m, _ in sweep.calls]
    out = []
    if modes != list(range(order)):
        out.append(f"MTTKRP modes {modes}, expected {list(range(order))}")
    if not np.isfinite(sweep.fit):
        out.append(f"fit {sweep.fit!r}")
    return out


def compare(sweeps: list[Sweep], coo: reference.COO, rank: int,
            norm_x: float, rng, controls: tuple = ()) -> dict:
    """The three numbers over a sample of ``sweeps``, and the faults found.

    For each precision in ``controls`` (such as ``"bfloat16"``) the same
    numbers are also read for the reference computed at that precision,
    put in the program's place, under ``"control"``.
    """
    order = len(coo.dims)
    picks = sample(len(sweeps), rng)
    cols = np.sort(rng.choice(rank, size=min(COLS, rank), replace=False))
    dirs = rng.standard_normal((rank, DIRS))
    faults = [f"sweep {i}: {f}" for i, s in enumerate(sweeps)
              for f in sweep_faults(s, order)]
    if not picks:
        faults.append("no sweep completed in the window")
    nums = dict.fromkeys(NUMBERS, 0.0)
    ctrl = {c: dict.fromkeys(NUMBERS, 0.0) for c in controls}
    out = {"numbers": nums, "control": ctrl, "faults": faults,
           "sweeps": picks, "cols": cols.tolist()}
    if faults:
        return out

    def worse(into, key, value):
        into[key] = max(into[key], float(value))

    for i in picks:
        s = sweeps[i]
        for k, (fin, mode, m_out) in enumerate(s.calls):
            fin = [_to_host(a) for a in fin]
            m_out = _to_host(m_out)
            ref = reference.mttkrp_cols(coo, fin, mode, cols)
            scale = np.max(np.abs(ref))
            worse(nums, "mttkrp_err", np.max(np.abs(m_out[:, cols] - ref))
                  / scale)
            if i == picks[-1]:
                ref_q = reference.mttkrp_dirs(coo, fin, mode, dirs)
                scale_q = np.max(np.abs(ref_q))
                worse(nums, "mttkrp_err",
                      np.max(np.abs(m_out @ dirs - ref_q)) / scale_q)
            want = reference.mode_update(m_out, fin, mode)
            new = _to_host(s.calls[k + 1][0][mode] if k + 1 < order
                           else s.factors[mode])
            worse(nums, "update_err", np.max(np.abs(new - want)))
            for c in controls:
                got = reference.mttkrp_cols(coo, fin, mode, cols, c)
                worse(ctrl[c], "mttkrp_err",
                      np.max(np.abs(got - ref)) / scale)
                if i == picks[-1]:
                    got = reference.mttkrp_dirs(coo, fin, mode, dirs, c)
                    worse(ctrl[c], "mttkrp_err",
                          np.max(np.abs(got - ref_q)) / scale_q)
                new = reference.mode_update(m_out, fin, mode, c)
                worse(ctrl[c], "update_err", np.max(np.abs(new - want)))
        fac = [_to_host(a) for a in s.factors]
        lam = _to_host(s.lam)
        m_last = _to_host(s.calls[-1][2])
        want, scale = reference.fit(norm_x, fac, lam, m_last)
        worse(nums, "fit_err", abs(s.fit - want) / scale)
        for c in controls:
            got, _ = reference.fit(norm_x, fac, lam, m_last, c)
            worse(ctrl[c], "fit_err", abs(got - want) / scale)
    return out


def verdict(result: dict, limits: dict) -> bool:
    """True when no fault was found and every number is within its limit."""
    return not result["faults"] and all(
        result["numbers"][k] <= limits[k] for k in result["numbers"])
