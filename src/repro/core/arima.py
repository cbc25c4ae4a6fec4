"""ARIMA(p, d, q) time-series model in JAX (paper §IV-A2).

The paper uses ARIMA to predict the timestamp of a program user's next
request, training on the n=60 most recent points.  We implement a standard
conditional-sum-of-squares (CSS) fit:

- difference the series ``d`` times,
- compute one-step-ahead residuals with a ``lax.scan`` over the ARMA(p, q)
  recursion ``e_t = y_t - c - Σ φ_i·y_{t-i} - Σ θ_j·e_{t-j}``,
- minimize ``Σ e_t²`` with jit-compiled Adam steps,
- forecast by iterating the recursion with future residuals set to zero and
  un-differencing through the saved per-level tails.

Everything is shape-static, so one compiled fit is reused across all users
with the same (n, p, d, q) — the compiled function is cached on first use.

Batched execution (the ARIMA *bank*)
------------------------------------

Every forecast — scalar ``forecast_next`` and :meth:`ARIMA.batched_forecast`
alike — executes through one ``jax.jit(jax.vmap(fit))`` program per history
bucket with a **fixed batch width** (:data:`BANK_WIDTH`).  Scalar calls pad
the batch by repeating the series; batch calls pack up to ``BANK_WIDTH``
users per dispatch.  Two properties make this the equivalence-safe design
(pinned by ``tests/test_hpm_equivalence.py``):

- vmapped rows are computed independently, so a row's forecast is bitwise
  identical regardless of batch position or what the other rows contain
  (padding included);
- scalar and batched paths therefore return *exactly* the same floats for
  the same series — the batched HPM planner's prefetch stream can be
  compared op-for-op against the online ``observe`` loop, and the 200-step
  Adam fit (whose trajectory is chaotic under any cross-compilation ulp
  difference) never needs cross-program reproducibility.

The cost is that an online (batch-of-one) fit pays for ``BANK_WIDTH`` rows;
the rows execute in SIMD lanes, so the padded call costs a small multiple of
the old scalar program while a *full* batch amortizes the scan overhead
~10-30x per fit (see ``BENCH_engine.json`` hpm scenarios).
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import telemetry

# Fixed batch width of every compiled fit program.  One width for all
# callers is what guarantees scalar/batched bitwise agreement; 32 sits at
# the knee of the CPU latency curve (a padded batch-of-one costs ~3-5x the
# old scalar program, a full batch ~10-30x less per fit).
BANK_WIDTH = 32

# History-length buckets: a series is truncated to the largest bucket that
# fits so only a handful of shapes are ever compiled (single-core CPU:
# compile time dominates otherwise).  ``ARIMA.n`` caps the last bucket.
_BUCKETS = (4, 8, 16, 32)


@dataclasses.dataclass(frozen=True)
class ARIMAOrder:
    p: int = 2
    d: int = 1
    q: int = 1


def _difference(y: jnp.ndarray, d: int) -> tuple[jnp.ndarray, list[jnp.ndarray]]:
    """Apply d-th order differencing; keep the last value at each level for
    later integration (``tails[k]`` = last value of the k-times-differenced
    series)."""
    tails = []
    for _ in range(d):
        tails.append(y[-1])
        y = jnp.diff(y)
    return y, tails


def _integrate(forecast, tails):
    """Undo :func:`_difference`: a forecast on the d-times-differenced scale
    plus the saved tails gives the forecast on the original scale.

    ``f^(k) = tails[k] + f^(k+1)`` applied from level d-1 down to 0 — the
    NumPy reference in ``tests/test_hpm_equivalence.py`` pins the same
    recurrence.
    """
    for tail in reversed(tails):
        forecast = tail + forecast
    return forecast


def _css_residuals(params: jnp.ndarray, y: jnp.ndarray, p: int, q: int) -> jnp.ndarray:
    """One-step-ahead residuals of an ARMA(p, q) on (already differenced) y."""
    c = params[0]
    phi = params[1 : 1 + p]
    theta = params[1 + p : 1 + p + q]
    n = y.shape[0]
    # state: (lagged y values [p], lagged residuals [q])
    y_hist0 = jnp.zeros((max(p, 1),), y.dtype)
    e_hist0 = jnp.zeros((max(q, 1),), y.dtype)

    def step(carry, y_t):
        y_hist, e_hist = carry
        pred = c
        if p:
            pred = pred + jnp.dot(phi, y_hist[:p])
        if q:
            pred = pred + jnp.dot(theta, e_hist[:q])
        e_t = y_t - pred
        y_hist = jnp.roll(y_hist, 1).at[0].set(y_t)
        e_hist = jnp.roll(e_hist, 1).at[0].set(e_t)
        return (y_hist, e_hist), e_t

    (_, _), resid = jax.lax.scan(step, (y_hist0, e_hist0), y)
    # discard the first max(p, q) warm-up residuals from the objective
    warm = max(p, q)
    mask = jnp.arange(n) >= warm
    return jnp.where(mask, resid, 0.0)


def _build_fit(n: int, p: int, d: int, q: int, steps: int, lr: float):
    """The (uncompiled) fit + one-step forecast for static shape (n,)."""

    def loss_fn(params, y):
        r = _css_residuals(params, y, p, q)
        return jnp.sum(r * r) / n

    grad_fn = jax.grad(loss_fn)

    def fit(y_raw: jnp.ndarray):
        # normalise for conditioning
        mu = jnp.mean(y_raw)
        sd = jnp.maximum(jnp.std(y_raw), 1e-8)
        y_n = (y_raw - mu) / sd
        y, tails = _difference(y_n, d)
        params0 = jnp.zeros((1 + p + q,), jnp.float32)

        def adam_step(carry, _):
            params, m, v, t = carry
            g = grad_fn(params, y)
            t = t + 1
            m = 0.9 * m + 0.1 * g
            v = 0.999 * v + 0.001 * g * g
            mh = m / (1 - 0.9 ** t)
            vh = v / (1 - 0.999 ** t)
            params = params - lr * mh / (jnp.sqrt(vh) + 1e-8)
            return (params, m, v, t), None

        init = (params0, jnp.zeros_like(params0), jnp.zeros_like(params0), 0.0)
        (params, _, _, _), _ = jax.lax.scan(adam_step, init, None, length=steps)

        # one-step forecast on the differenced scale
        resid = _css_residuals(params, y, p, q)
        c = params[0]
        phi = params[1 : 1 + p]
        theta = params[1 + p : 1 + p + q]
        fy = c
        if p:
            fy = fy + jnp.dot(phi, y[::-1][:p])
        if q:
            fy = fy + jnp.dot(theta, resid[::-1][:q])
        # integrate the d differences back through the saved tails
        forecast = _integrate(fy, tails) * sd + mu
        return forecast, params

    return fit


@functools.lru_cache(maxsize=16)
def _compiled_fit(n: int, p: int, d: int, q: int, steps: int, lr: float):
    """jit-compiled single-series (fit + forecast) for static shapes.

    Kept for direct unit testing of the fit; the forecast API below runs
    everything through the batched bank program instead.
    """
    return jax.jit(_build_fit(n, p, d, q, steps, lr))


@functools.lru_cache(maxsize=16)
def _compiled_bank(n: int, p: int, d: int, q: int, steps: int, lr: float):
    """The bank program: jit(vmap(fit)) over a fixed (BANK_WIDTH, n) batch,
    returning only the forecasts (params stay on device).  It is named
    ``arima_bank_n{n}``, so a profile shows it as ``jit_arima_bank_n{n}``."""
    fit = _build_fit(n, p, d, q, steps, lr)
    bank = jax.vmap(lambda y: fit(y)[0])
    bank.__name__ = bank.__qualname__ = f"arima_bank_n{n}"
    return jax.jit(bank)


class ARIMA:
    """Stateful wrapper mirroring the paper's usage: fit on the n most recent
    points, forecast the next one.

    ``bank=False`` dispatches the single-series compiled program instead of
    the fixed-width bank: ~BANK_WIDTH× less compute per scalar call, but the
    results are NOT bitwise comparable with any bank-routed model.  Only
    models whose forecasts are compared across online and batched execution
    (hpm) need the default; consumers that predict the same way everywhere —
    md2 predicts online in both replay engines, the serving scheduler sits
    outside replay entirely — should opt out.
    """

    def __init__(self, order: ARIMAOrder = ARIMAOrder(), n: int = 60,
                 steps: int = 200, lr: float = 0.05, bank: bool = True):
        self.order = order
        self.n = n
        self.steps = steps
        self.lr = lr
        self.bank = bank

    def _bucket(self, size: int) -> int:
        """Largest compiled history length that fits ``size`` points."""
        buckets = [b for b in (*_BUCKETS, self.n)
                   if b <= min(size, self.n)]
        return buckets[-1]

    def _bank(self, n: int):
        o = self.order
        return _compiled_bank(n, o.p, o.d, o.q, self.steps, self.lr)

    def forecast_next(self, series: np.ndarray) -> float:
        """Forecast the next value of ``series`` (e.g. inter-arrival gaps).

        Equivalence obligation: with ``bank=True`` (the default) the scalar
        call pads a batch through the SAME fixed-width compiled bank
        program that :meth:`batched_forecast` runs, so online and batched
        prediction are bitwise identical (``tests/test_hpm_equivalence.py``
        pins this); ``bank=False`` opts out for latency-sensitive callers
        outside the equivalence contract.
        """
        if not self.bank:
            series = np.asarray(series, dtype=np.float32)
            if series.size < 4:
                return float(series[-1]) if series.size else 0.0
            n = self._bucket(series.size)
            y = series[-n:]
            o = self.order
            fit = _compiled_fit(n, o.p, o.d, o.q, self.steps, self.lr)
            out = float(fit(jnp.asarray(y))[0])
            return out if np.isfinite(out) else float(np.median(y))
        return float(self.batched_forecast([series])[0])

    def batched_forecast(self, series_list) -> np.ndarray:
        """Forecast the next value of each (ragged) series in one pass.

        Semantics per series are identical to :meth:`forecast_next` — the
        <4-point last-value fallback, history bucketing and the median
        fallback for non-finite fits all apply row-wise — and the returned
        floats are bitwise equal to per-series calls (fixed-width bank, see
        module docstring).  Series are grouped by bucket and fitted
        ``BANK_WIDTH`` per compiled call; short batches are padded by
        repeating the first row (padding rows are computed independently and
        discarded).  A ``bank=False`` model falls back to per-series scalar
        dispatch (no grouping, no padding — and no bitwise batch contract).
        """
        if not self.bank:
            return np.array([self.forecast_next(s) for s in series_list],
                            dtype=np.float64)
        out = np.empty(len(series_list), dtype=np.float64)
        by_bucket: dict[int, list[tuple[int, np.ndarray]]] = {}
        for i, series in enumerate(series_list):
            series = np.asarray(series, dtype=np.float32)
            if series.size < 4:
                # not enough history: fall back to the last value
                out[i] = float(series[-1]) if series.size else 0.0
                continue
            n = self._bucket(series.size)
            by_bucket.setdefault(n, []).append((i, series[-n:]))
        if not by_bucket:
            return out
        with telemetry.span("vdc.arima.flush") as sp:
            calls = used = 0
            for n, tasks in by_bucket.items():
                bank = self._bank(n)
                pending = []
                for lo in range(0, len(tasks), BANK_WIDTH):
                    chunk = tasks[lo:lo + BANK_WIDTH]
                    rows = np.empty((BANK_WIDTH, n), np.float32)
                    for j, (_, y) in enumerate(chunk):
                        rows[j] = y
                    if len(chunk) < BANK_WIDTH:
                        rows[len(chunk):] = rows[0]
                    # dispatch is async; sync once per bucket below
                    pending.append((chunk, bank(jnp.asarray(rows))))
                for chunk, fc in pending:
                    fc = np.asarray(fc, dtype=np.float64)
                    for j, (i, y) in enumerate(chunk):
                        v = fc[j]
                        out[i] = v if np.isfinite(v) else float(np.median(y))
                calls += len(pending)
                used += len(tasks)
            sp.count(bank_calls=calls, bank_rows=used,
                     bank_pad_rows=calls * BANK_WIDTH - used)
        return out


def _gap_stats(g: list[float]) -> tuple[float, float, bool]:
    """(median gap, max gap, fast-path?) for an inter-arrival gap list.

    The gap window is ≤ a couple hundred points and this runs once per
    observed request: plain-Python median/std beat the NumPy dispatch
    overhead by ~20x here.  This is the one definition of the
    near-constant-gap decision below: the online path and
    ``predict_next_timestamp(s)`` call it per request, and the batched
    planner (``repro.core.hpm._PlannedUser.gap_stats``) decides from
    running sums only outside a proven rounding margin of the threshold
    and calls it inside, so every path decides bit for bit alike (an
    unguarded vectorized reimplementation could flip a knife-edge series).

    Near-constant inter-arrivals (scripted cron-style consumers): ARIMA's
    forecast collapses to the median gap; skip the fit.  This is the common
    case for program users and keeps the online engine cheap.
    """
    gs = sorted(g)
    n = len(gs)
    mid = n // 2
    med = gs[mid] if n % 2 else (gs[mid - 1] + gs[mid]) / 2.0
    fast = False
    if med > 0:
        mean = sum(g) / n
        std = (sum((x - mean) ** 2 for x in g) / n) ** 0.5
        fast = std / med < 0.02
    return med, gs[-1], fast


def clamp_forecast_gap(last_ts: float, gap: float, max_gap: float) -> float:
    """Forecast post-processing: clamp the predicted gap to [0, 10·max_gap]
    and advance the last timestamp.  One shared definition for the scalar,
    batched and planner paths — part of the bitwise online==batched
    contract, like :func:`_gap_stats`."""
    return float(last_ts + min(max(gap, 0.0), 10 * max_gap))


def predict_next_timestamp(timestamps: np.ndarray, model: ARIMA | None = None) -> float:
    """Predict ts_{i+1} from past request timestamps (paper §IV-A2): model the
    inter-arrival gap series and add the forecast gap to the last timestamp."""
    timestamps = np.asarray(timestamps, dtype=np.float64)
    if timestamps.size < 2:
        return float(timestamps[-1]) if timestamps.size else 0.0
    gaps = np.diff(timestamps)
    med, max_gap, fast = _gap_stats(gaps.tolist())
    if fast:
        return float(timestamps[-1] + med)
    model = model or ARIMA()
    gap = model.forecast_next(gaps.astype(np.float32))
    return clamp_forecast_gap(float(timestamps[-1]), gap, max_gap)


def predict_next_timestamps(series_list, model: ARIMA | None = None) -> np.ndarray:
    """Batched :func:`predict_next_timestamp` over many timestamp series.

    Fast-path decisions reuse :func:`_gap_stats` and ARIMA-bound series are
    flushed through :meth:`ARIMA.batched_forecast` in one pass, so each
    element is bitwise equal to the scalar call on the same series."""
    model = model or ARIMA()
    out = np.empty(len(series_list), dtype=np.float64)
    pending: list[tuple[int, np.ndarray, float, float]] = []
    for i, ts in enumerate(series_list):
        ts = np.asarray(ts, dtype=np.float64)
        if ts.size < 2:
            out[i] = float(ts[-1]) if ts.size else 0.0
            continue
        gaps = np.diff(ts)
        med, max_gap, fast = _gap_stats(gaps.tolist())
        if fast:
            out[i] = float(ts[-1] + med)
            continue
        pending.append((i, gaps.astype(np.float32), float(ts[-1]), max_gap))
    if pending:
        forecasts = model.batched_forecast([p[1] for p in pending])
        for (i, _, last, max_gap), gap in zip(pending, forecasts):
            out[i] = clamp_forecast_gap(last, float(gap), max_gap)
    return out
