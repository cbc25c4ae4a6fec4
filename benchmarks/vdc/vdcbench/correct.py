"""The correctness check: what the timed path produced against the plain
reference (:mod:`vdcbench.ref`), request by request and counter by counter.

The program's side is captured from the first job of the measured window
(:class:`vdcbench.probes.Probes`): every request's outcome columns and
prefetch ops (planned in windows or predicted online), and the engine's
integer counters at the end of each stream window.  The reference replays the same replay split, online, over
the cell's first ``check.windows`` stream windows and stops right after
the last request of that prefix, where the program's counters were read.
The numbers compared, each against its own limit:

- ``missing``: requests of the prefix the program produced no answer for;
- ``ops_differ``: requests whose prefetch ops (issue time, user, object,
  time range, reason — stream hand-offs included) are not exactly the
  reference's;
- ``outcomes_differ``: requests whose integer outcome (bytes served, and
  their split into local, prefetched, peer and origin bytes) is not
  exactly the reference's;
- ``counters_differ``: counters at the end of the prefix (per DTN: hits,
  misses, hit and missed bytes, evictions, inserted bytes; stream pushes)
  that are not exactly the reference's, or that the program never reported;
- ``delivery_gap``: the largest relative gap between a request's simulated
  delivery time (origin queue wait plus transfer) and the reference's.

The limits and the readings they were set from are in ``PERF.md``.
"""
from __future__ import annotations

import dataclasses

import numpy as np

from .ref import simulator as ref_sim

LIMITS = {
    "missing": 0,
    "ops_differ": 0,
    "outcomes_differ": 0,
    "counters_differ": 0,
    "delivery_gap": 1e-9,
}

_INT_FIELDS = (("bytes", "bytes"), ("local", "local_bytes"),
               ("prefetched", "prefetched_bytes"), ("peer", "peer_bytes"),
               ("origin", "origin_bytes"))
_GAP_FLOOR_S = 1e-12


@dataclasses.dataclass
class Verdict:
    numbers: dict

    @property
    def correct(self) -> bool:
        return all(self.numbers[k] <= LIMITS[k] for k in LIMITS)

    def as_dict(self) -> dict:
        return {k: {"value": self.numbers[k], "limit": LIMITS[k]}
                for k in LIMITS}

    def lines(self) -> list[str]:
        return [f"check {k}: {self.numbers[k]!r} (limit {LIMITS[k]!r})"
                for k in LIMITS]


def _canon(ops) -> tuple:
    return tuple((float(o.issue_ts), int(o.user_id), int(o.obj),
                  float(o.tr_start), float(o.tr_end), str(o.reason))
                 for o in ops)


def columns_of(outcomes) -> dict:
    """Reference outcomes in the program's column form (for the control,
    which puts a reference in the program's place)."""
    return {
        "bytes": np.array([o.bytes for o in outcomes], np.int64),
        "latency": np.array([o.latency for o in outcomes], np.float64),
        "transfer": np.array([o.transfer_time for o in outcomes], np.float64),
        "local": np.array([o.local_bytes for o in outcomes], np.int64),
        "prefetched": np.array([o.prefetched_bytes for o in outcomes], np.int64),
        "peer": np.array([o.peer_bytes for o in outcomes], np.int64),
        "origin": np.array([o.origin_bytes for o in outcomes], np.int64),
    }


def prefix_length(spec: dict, n_test: int) -> int:
    """Requests in the checked prefix: the first ``check.windows`` stream
    windows."""
    return min(n_test, spec["check"]["windows"] * spec["stream_window"])


def compare(columns, ops, counters, ref_outcomes, ref_ops,
            ref_counters) -> Verdict:
    """Compare the program's answers (``columns``, ``ops``, ``counters``;
    any may be ``None`` when nothing was captured) with the reference's,
    request by request over the requests the reference answered, and
    counter by counter."""
    n = len(ref_outcomes)
    have = 0 if columns is None else min(n, len(columns["bytes"]))
    if ops is not None:
        have = min(have, len(ops))
    ops_differ = outcomes_differ = 0
    gap = 0.0
    for i in range(have):
        o = ref_outcomes[i]
        mine = _canon(ops[i]) if ops is not None else ()
        if mine != _canon(ref_ops[i]):
            ops_differ += 1
        if any(int(columns[c][i]) != getattr(o, f) for c, f in _INT_FIELDS):
            outcomes_differ += 1
        d_prog = float(columns["latency"][i]) + float(columns["transfer"][i])
        d_ref = o.latency + o.transfer_time
        g = abs(d_prog - d_ref) / max(abs(d_ref), _GAP_FLOOR_S)
        if not g <= gap:        # also catches NaN
            gap = float(g) if np.isfinite(g) else float("inf")
    counters = counters or {}
    counters_differ = 0
    for key, want in ref_counters.items():
        got = counters.get(key, ())
        counters_differ += sum(
            1 for i, v in enumerate(want)
            if i >= len(got) or int(got[i]) != int(v))
    return Verdict({"missing": n - have, "ops_differ": ops_differ,
                    "outcomes_differ": outcomes_differ,
                    "counters_differ": counters_differ,
                    "delivery_gap": gap})


def reference(spec: dict, test, train, grid, cfg, arima_dtype: str = "float32"):
    """The plain reference over the checked prefix of ``test``:
    ``(outcomes, ops, counters)``."""
    return ref_sim.replay(
        spec["strategy"], test[:prefix_length(spec, len(test))], grid, cfg,
        train, arima_dtype=arima_dtype)


def check(probes, spec: dict, test, train, grid, cfg) -> Verdict:
    """The program's captured job against the reference."""
    ref_outcomes, ref_ops, ref_counters = reference(spec, test, train, grid,
                                                    cfg)
    return compare(probes.columns, probes.ops,
                   probes.counters_after(len(ref_outcomes)),
                   ref_outcomes, ref_ops, ref_counters)
