"""ARIMA bank program calls made in the window (a count)."""


def read(ctx):
    n = ctx.counters.get("bank_calls")
    return float(n) if n else None
