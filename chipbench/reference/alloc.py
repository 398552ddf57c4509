"""Plain reference of the paper's spectrum allocation for one round, in
NumPy at a stated dtype (float64 for the reference).

``sao``: Algorithm 5 (problem (19)): an outer bisection on the round
latency T until the band is used to within eps0, inner bisections per
device for the CPU frequency (the cubic (23), clipped to its box) and the
bandwidth (the tight energy budget (21)), then the final frequencies from
the clipped bandwidths and the true delay (20). The constants (48 outer
and 48 inner steps, eps0 = 1e-3, the brackets) are the algorithm's.

``equal``: Baseline 1 (section VI-A): b = B/S, each device at the fastest
frequency its energy budget allows.

Both return ``(T_k, E_k)`` (eqs. 10-11) for the devices ``sel`` of
``fleet`` (the arrays of ``generate.fleet``).
"""
from __future__ import annotations

import numpy as np

N_OUTER = 48
N_INNER = 48
EPS0 = 1e-3


class _Arith:
    """Scalars and casts in one dtype (NumPy would promote bfloat16)."""

    def __init__(self, dt):
        self.dt = dt

    def __call__(self, x):
        return np.asarray(x).astype(self.dt)

    def k(self, v):
        return np.asarray(v, self.dt)


def _Q(a, b, J):
    """Q(b) = b log2(1 + J/b), eq. (7) in MHz and Mbit/s."""
    bb = np.maximum(b, a.k(1e-12))
    return a(bb * np.log2(a.k(1.0) + a(J / bb)))


def _arrays(fleet, sel, dt):
    return {n: np.asarray(fleet[n], np.float64)[sel].astype(dt)
            for n in ("J", "U", "G", "H", "z", "e_cons", "f_min", "f_max")}


def _bisect(a, lo, hi, above, iters):
    half = a.k(0.5)
    for _ in range(iters):
        mid = a((lo + hi) * half)
        up = above(mid)
        lo, hi = np.where(up, lo, mid), np.where(up, mid, hi)
    return a((lo + hi) * half)


def _inner(a, T, x, b_max):
    """Alg. 5 lines 5-11: f from the cubic (23), boxed; b from (21)."""
    X = a(a(x["H"] * T) / a(x["z"] * x["G"])) - a(x["e_cons"] / x["G"])
    Y = a(a(x["H"] * x["U"]) / a(x["z"] * x["G"]))
    hi = a(np.cbrt(Y) + np.sqrt(a(np.maximum(-X, a.k(0.0)) / a.k(3.0)))
           + a.k(1.0))
    f = _bisect(a, a(np.zeros_like(Y)), hi,
                lambda m: a(a(m * m) * m + a(X * m) - Y) > 0, N_INNER)
    f = np.clip(f, x["f_min"], x["f_max"])
    resid = a(x["e_cons"] - a(x["G"] * a(f * f)))
    target = a(x["H"] / np.maximum(resid, a.k(1e-12)))
    ok = ((resid > 0) & (target < a(x["J"] / a.k(np.log(2.0))))
          & (_Q(a, b_max, x["J"]) >= target))
    b = _bisect(a, a(np.full_like(f, 1e-9)), a(np.full_like(f, b_max)),
                lambda m: _Q(a, m, x["J"]) >= target, N_INNER)
    return np.where(ok, b, b_max), f


def sao(fleet, sel, B, dt=np.float64, lanes=None):
    """Algorithm 5 on devices ``sel``; ``lanes`` is the size of the round's
    selection slate (its lower bandwidth floor B/lanes/1000 sets the
    initial upper bracket of T), by default ``len(sel)``."""
    a = _Arith(dt)
    x = _arrays(fleet, sel, dt)
    B = a.k(B)
    b_max = B
    n = len(sel) if lanes is None else lanes
    T_lo = np.max(a(a(a.k(np.log(2.0)) * x["z"]) / x["J"]
                    + a(x["U"] / x["f_max"])))
    b_floor = np.maximum(a(B / a.k(n) * a.k(1e-3)), a.k(1e-6))
    T_hi = a(np.max(a(x["z"] / _Q(a, b_floor, x["J"])
                      + a(x["U"] / x["f_min"]))) * a.k(2.0))
    half = a.k(0.5)
    for _ in range(N_OUTER):
        T = a((T_lo + T_hi) * half)
        b, _ = _inner(a, T, x, b_max)
        ratio = a(np.sum(b) / B)
        done = (ratio <= 1.0) and (ratio >= 1.0 - EPS0)
        if done:
            T_lo = T_hi = T
            break
        if ratio > 1.0:
            T_lo = T
        if ratio < 1.0 - EPS0:
            T_hi = T
    T = a((T_lo + T_hi) * half)
    b, f = _inner(a, T, x, b_max)
    Qb = _Q(a, b, x["J"])
    resid = a(x["e_cons"] - a(x["H"] / Qb))
    f_star = np.clip(a(np.sqrt(a(np.maximum(resid, a.k(0.0)) / x["G"]))),
                     x["f_min"], x["f_max"])
    e_star = a(a(x["G"] * a(f_star * f_star)) + a(x["H"] / Qb))
    f = np.where(e_star <= a(x["e_cons"] + a.k(1e-6)), f_star, f)
    t = a(a(x["z"] / Qb) + a(x["U"] / f))
    e = a(a(x["G"] * a(f * f)) + a(x["H"] / Qb))
    return float(np.max(t)), float(np.sum(e.astype(np.float64)))


def equal(fleet, sel, B, dt=np.float64, lanes=None):
    """Baseline 1 on devices ``sel``."""
    a = _Arith(dt)
    x = _arrays(fleet, sel, dt)
    b = a(np.full(len(sel), B / len(sel)))
    Qb = _Q(a, b, x["J"])
    ecom = a(x["H"] / Qb)
    f = np.clip(a(np.sqrt(a(np.maximum(a(x["e_cons"] - ecom), a.k(0.0))
                            / x["G"]))), x["f_min"], x["f_max"])
    t = a(a(x["z"] / Qb) + a(x["U"] / f))
    e = a(a(x["G"] * a(f * f)) + ecom)
    return float(np.max(t)), float(np.sum(e.astype(np.float64)))


ALLOCATORS = {"sao": sao, "equal": equal}
