"""ARIMA(p, d, q) next-gap forecasts through a fixed-width JAX bank
(paper §IV-A2).

Frozen copy of the bank path of ``src/repro/core/arima.py`` at commit
bcb7c9a: conditional-sum-of-squares fit by 200 Adam steps over a
``lax.scan`` residual recursion, one ``jit(vmap(fit))`` program per history
bucket at a fixed batch width, online calls padded to that width.

One addition: ``dtype``.  ``float32`` (the configuration's precision)
builds the same program as the copied code; ``bfloat16`` is the
lower-precision control that the correctness check must reject.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

BANK_WIDTH = 32
_BUCKETS = (4, 8, 16, 32)


def _difference(y, d: int):
    tails = []
    for _ in range(d):
        tails.append(y[-1])
        y = jnp.diff(y)
    return y, tails


def _integrate(forecast, tails):
    for tail in reversed(tails):
        forecast = tail + forecast
    return forecast


def _css_residuals(params, y, p: int, q: int):
    """One-step-ahead residuals of an ARMA(p, q) on (already differenced) y."""
    c = params[0]
    phi = params[1 : 1 + p]
    theta = params[1 + p : 1 + p + q]
    n = y.shape[0]
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
    warm = max(p, q)
    mask = jnp.arange(n) >= warm
    return jnp.where(mask, resid, 0.0)


def _build_fit(n: int, p: int, d: int, q: int, steps: int, lr: float,
               dtype):
    def loss_fn(params, y):
        r = _css_residuals(params, y, p, q)
        return jnp.sum(r * r) / n

    grad_fn = jax.grad(loss_fn)

    def fit(y_raw):
        mu = jnp.mean(y_raw)
        sd = jnp.maximum(jnp.std(y_raw), 1e-8)
        y_n = (y_raw - mu) / sd
        y, tails = _difference(y_n, d)
        params0 = jnp.zeros((1 + p + q,), dtype)

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

        resid = _css_residuals(params, y, p, q)
        c = params[0]
        phi = params[1 : 1 + p]
        theta = params[1 + p : 1 + p + q]
        fy = c
        if p:
            fy = fy + jnp.dot(phi, y[::-1][:p])
        if q:
            fy = fy + jnp.dot(theta, resid[::-1][:q])
        forecast = _integrate(fy, tails) * sd + mu
        return forecast, params

    return fit


@functools.lru_cache(maxsize=32)
def _compiled_bank(n: int, p: int, d: int, q: int, steps: int, lr: float,
                   dtype_name: str):
    fit = _build_fit(n, p, d, q, steps, lr, jnp.dtype(dtype_name))
    return jax.jit(jax.vmap(lambda y: fit(y)[0]))


class ARIMA:
    """Fit on the n most recent points, forecast the next one."""

    def __init__(self, n: int = 60, p: int = 2, d: int = 1, q: int = 1,
                 steps: int = 200, lr: float = 0.05,
                 dtype: str = "float32"):
        self.n, self.p, self.d, self.q = n, p, d, q
        self.steps = steps
        self.lr = lr
        self.dtype = dtype

    def _bucket(self, size: int) -> int:
        buckets = [b for b in (*_BUCKETS, self.n) if b <= min(size, self.n)]
        return buckets[-1]

    def forecast_next(self, series: np.ndarray) -> float:
        """Forecast the next value of ``series`` through one padded bank
        call."""
        series = np.asarray(series, dtype=np.float32)
        if series.size < 4:
            return float(series[-1]) if series.size else 0.0
        n = self._bucket(series.size)
        y = series[-n:]
        rows = np.empty((BANK_WIDTH, n), np.float32)
        rows[:] = y
        bank = _compiled_bank(n, self.p, self.d, self.q, self.steps, self.lr,
                              self.dtype)
        fc = np.asarray(bank(jnp.asarray(rows, dtype=self.dtype)),
                        dtype=np.float64)
        v = fc[0]
        return float(v) if np.isfinite(v) else float(np.median(y))


def _gap_stats(g: list[float]) -> tuple[float, float, bool]:
    """(median gap, max gap, near-constant?) for an inter-arrival gap list."""
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


def predict_next_timestamp(timestamps: np.ndarray, model: ARIMA) -> float:
    """Predict ts_{i+1} from past request timestamps: forecast the next
    inter-arrival gap and add it to the last timestamp."""
    timestamps = np.asarray(timestamps, dtype=np.float64)
    if timestamps.size < 2:
        return float(timestamps[-1]) if timestamps.size else 0.0
    gaps = np.diff(timestamps)
    med, max_gap, fast = _gap_stats(gaps.tolist())
    if fast:
        return float(timestamps[-1] + med)
    gap = model.forecast_next(gaps.astype(np.float32))
    return float(timestamps[-1] + min(max(gap, 0.0), 10 * max_gap))
