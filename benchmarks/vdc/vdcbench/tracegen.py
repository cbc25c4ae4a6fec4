"""The benchmark's own trace generator: a frozen copy of the program's
calibrated OOI/GAGE synthesizer.

Copied from ``src/repro/core/trace.py`` at commit bcb7c9a (``Request``,
``ObjectGrid``, ``TraceProfile``, ``OOI_PROFILE``, ``GAGE_PROFILE``,
``_plan_program_users`` and ``TraceGenerator``), so that later changes to
the program's generator do not move the yardstick.  Added outside the
copied arithmetic:

- :func:`profile_from_dict` builds a profile from a configuration file;
- :func:`relabel` renames a trace's objects and users by permutations drawn
  from a seed.  A run's trace is drawn from :data:`SHAPE_SEED` and renamed
  by ``--seed``: every seed replays the same arrivals, ranges and sizes,
  so the same work, under other object and user ids.  Drawn from
  ``--seed`` itself, the trace moved one OOI job's time by 8% from seed to
  seed on a TPU v5e host.
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Sequence

import numpy as np

HOUR = 3600.0
DAY = 24 * HOUR
WEEK = 7 * DAY
MINUTE = 60.0
SHAPE_SEED = 0


@dataclasses.dataclass(frozen=True, slots=True)
class Request:
    """One entry of an observatory access log (paper §III, Eq. 1)."""

    ts: float                 # access timestamp (s since trace start)
    user_id: int
    obj: int                  # serialized data-object id (instrument, location)
    tr_start: float           # requested range start (observation time, s)
    tr_end: float             # requested range end
    size_bytes: int
    continent: int            # 0..5 (six continents, Antarctica excluded)


@dataclasses.dataclass(frozen=True, slots=True)
class ObjectGrid:
    """Instrument catalog: ``n_types`` instrument types x ``n_locs``
    locations; object ids are ``type * n_locs + loc``."""

    n_types: int
    n_locs: int

    @property
    def n_objects(self) -> int:
        return self.n_types * self.n_locs

    def obj_id(self, itype: int, loc: int) -> int:
        return itype * self.n_locs + loc

    def type_of(self, obj: int) -> int:
        return obj // self.n_locs

    def loc_of(self, obj: int) -> int:
        return obj % self.n_locs


@dataclasses.dataclass(frozen=True)
class TraceProfile:
    """Calibration constants for one observatory (Tables I & II + Fig 2)."""

    name: str
    n_users: int
    duration: float
    human_user_frac: float
    program_volume_frac: float
    type_volume_mix: tuple[float, float, float]
    overlap_duplicate_frac: float
    continent_probs: tuple[float, ...]
    bytes_per_second_stream: float
    grid: ObjectGrid
    period_jitter_frac: float = 0.01


GAGE_PROFILE = TraceProfile(
    name="gage", n_users=600, duration=8 * WEEK, human_user_frac=0.941,
    program_volume_frac=0.906, type_volume_mix=(0.772, 0.061, 0.172),
    overlap_duplicate_frac=0.896,
    continent_probs=(0.28, 0.37, 0.18, 0.07, 0.04, 0.06),
    bytes_per_second_stream=2e3, grid=ObjectGrid(n_types=24, n_locs=40),
)

OOI_PROFILE = TraceProfile(
    name="ooi", n_users=400, duration=4 * WEEK, human_user_frac=0.867,
    program_volume_frac=0.901, type_volume_mix=(0.138, 0.257, 0.608),
    overlap_duplicate_frac=0.904,
    continent_probs=(0.62, 0.12, 0.14, 0.05, 0.02, 0.05),
    bytes_per_second_stream=8e3, grid=ObjectGrid(n_types=30, n_locs=30),
)


def profile_from_dict(d: dict) -> TraceProfile:
    """A :class:`TraceProfile` from a configuration file's ``profile``
    object (``duration_s`` in seconds, ``grid`` as ``{n_types, n_locs}``)."""
    return TraceProfile(
        name=d["name"], n_users=int(d["n_users"]),
        duration=float(d["duration_s"]),
        human_user_frac=float(d["human_user_frac"]),
        program_volume_frac=float(d["program_volume_frac"]),
        type_volume_mix=tuple(float(v) for v in d["type_volume_mix"]),
        overlap_duplicate_frac=float(d["overlap_duplicate_frac"]),
        continent_probs=tuple(float(v) for v in d["continent_probs"]),
        bytes_per_second_stream=float(d["bytes_per_second_stream"]),
        grid=ObjectGrid(int(d["grid"]["n_types"]), int(d["grid"]["n_locs"])),
        period_jitter_frac=float(d["period_jitter_frac"]),
    )


def _normalize(v: Sequence[float]) -> np.ndarray:
    a = np.asarray(v, dtype=np.float64)
    return a / a.sum()


def _zipf_probs(n: int, alpha: float = 1.1) -> np.ndarray:
    ranks = np.arange(1, n + 1, dtype=np.float64)
    p = ranks ** (-alpha)
    return p / p.sum()


def _plan_program_users(profile: TraceProfile, rng: np.random.Generator,
                        n_program: int) -> list[dict]:
    """Assign each program user a behaviour; user counts follow the volume
    mix."""
    p = profile
    mix = _normalize(p.type_volume_mix)
    dup = p.overlap_duplicate_frac
    k_overlap = max(2, int(round(1.0 / max(1e-6, 1.0 - dup))))
    n_by_type = np.maximum(1, np.round(mix * n_program)).astype(int)
    per_type: list[list[dict]] = [[], [], []]
    for btype, n in enumerate(n_by_type):
        for _ in range(int(n)):
            if btype == 0:      # regular
                period = float(rng.choice([HOUR, 2 * HOUR, 6 * HOUR]))
                window = period
            elif btype == 1:    # real-time
                period = MINUTE
                window = MINUTE
            else:               # overlapping
                period = HOUR
                window = k_overlap * HOUR
            per_type[btype].append(
                dict(
                    behaviour=("regular", "realtime", "overlapping")[btype],
                    period=period,
                    window=window,
                    n_streams=int(rng.integers(1, 4)),
                )
            )
    # round-robin across types so truncation keeps type diversity
    plans: list[dict] = []
    for group in itertools.zip_longest(*per_type):
        plans.extend(p for p in group if p is not None)
    return plans[:n_program] if len(plans) > n_program else plans


class TraceGenerator:
    """Synthesize an access trace calibrated to a :class:`TraceProfile`
    (program users: regular, real-time, overlapping; human users: short
    spatially correlated browsing sessions)."""

    def __init__(self, profile: TraceProfile, seed: int = 0):
        self.profile = profile
        self.rng = np.random.default_rng(seed)

    def _program_user_plan(self, n_program: int) -> list[dict]:
        return _plan_program_users(self.profile, self.rng, n_program)

    def _gen_program_requests(
        self, user_id: int, plan: dict, continent: int
    ) -> list[Request]:
        p = self.profile
        period, window = plan["period"], plan["window"]
        if plan["behaviour"] == "realtime":
            span = min(p.duration, 3 * DAY)
        else:
            span = p.duration
        start = float(self.rng.uniform(0, period))
        objs = self.rng.choice(p.grid.n_objects, size=plan["n_streams"],
                               replace=False,
                               p=_zipf_probs(p.grid.n_objects, alpha=1.0))
        out: list[Request] = []
        t = start
        overlapping = plan["behaviour"] == "overlapping"
        last_end: dict[int, float] = {}
        while t < span:
            jitter = float(self.rng.normal(0.0, p.period_jitter_frac * period))
            ts = max(0.0, t + jitter)
            for obj in objs:
                tr_end = ts
                if overlapping:
                    tr_start = max(0.0, ts - window)
                else:
                    tr_start = last_end.get(int(obj), max(0.0, ts - window))
                    last_end[int(obj)] = tr_end
                size = int((tr_end - tr_start) * p.bytes_per_second_stream)
                out.append(
                    Request(ts, user_id, int(obj), tr_start, tr_end, size, continent)
                )
            t += period
        return out

    def _gen_human_requests(self, user_id: int, continent: int) -> list[Request]:
        p = self.profile
        g = p.grid
        n_sessions = int(self.rng.integers(1, 4))
        out: list[Request] = []
        type_pop = _zipf_probs(g.n_types)
        for _ in range(n_sessions):
            t0 = float(self.rng.uniform(0, p.duration))
            loc = int(self.rng.integers(0, g.n_locs))
            itype = int(self.rng.choice(g.n_types, p=type_pop))
            n_req = int(self.rng.integers(3, 12))
            t = t0
            for _ in range(n_req):
                if self.rng.random() < 0.5:
                    itype = int(self.rng.choice(g.n_types, p=type_pop))
                else:
                    loc = int(np.clip(loc + self.rng.integers(-2, 3), 0, g.n_locs - 1))
                obj = g.obj_id(itype, loc)
                window = float(self.rng.choice([HOUR, 6 * HOUR, DAY]))
                tr_end = float(self.rng.uniform(0, max(1.0, t - 1.0))) if t > 2 else t
                tr_start = max(0.0, tr_end - window)
                size = int((tr_end - tr_start) * p.bytes_per_second_stream * 0.1)
                out.append(Request(t, user_id, obj, tr_start, tr_end, size, continent))
                t += float(self.rng.exponential(120.0))
        return out

    def generate(self) -> list[Request]:
        p = self.profile
        n_human = int(round(p.n_users * p.human_user_frac))
        n_program = p.n_users - n_human
        cont_p = _normalize(p.continent_probs)
        plans = self._program_user_plan(n_program)
        uid = 0
        by_type: dict[str, list[Request]] = {
            "regular": [], "realtime": [], "overlapping": []}
        for plan in plans:
            cont = int(self.rng.choice(6, p=cont_p))
            by_type[plan["behaviour"]].extend(
                self._gen_program_requests(uid, plan, cont))
            uid += 1
        human: list[Request] = []
        for _ in range(n_human):
            cont = int(self.rng.choice(6, p=cont_p))
            human.extend(self._gen_human_requests(uid, cont))
            uid += 1

        # exact volume calibration (Tables I & II)
        mix = _normalize(p.type_volume_mix)
        order = ("regular", "realtime", "overlapping")
        totals = np.array(
            [max(1, sum(r.size_bytes for r in by_type[t])) for t in order],
            dtype=np.float64,
        )
        target = mix / mix[0] * totals[0]
        mult = target / totals
        program: list[Request] = []
        for t, m in zip(order, mult):
            for r in by_type[t]:
                program.append(
                    dataclasses.replace(r, size_bytes=max(1, int(r.size_bytes * m)))
                )
        prog_total = sum(r.size_bytes for r in program)
        hum_total = max(1, sum(r.size_bytes for r in human))
        h_frac = 1.0 - p.program_volume_frac
        h_factor = (prog_total * h_frac / max(1e-9, p.program_volume_frac)) / hum_total
        human = [
            dataclasses.replace(r, size_bytes=max(1, int(r.size_bytes * h_factor)))
            for r in human
        ]
        requests = program + human
        requests.sort(key=lambda r: r.ts)
        return requests


def relabel(requests: Sequence[Request], profile: TraceProfile,
            seed: int) -> list[Request]:
    """``requests`` with object and user ids renamed by permutations drawn
    from ``seed``; arrivals, ranges, sizes, continents and order unchanged."""
    rng = np.random.default_rng(seed)
    objs = rng.permutation(profile.grid.n_objects).tolist()
    users = rng.permutation(profile.n_users).tolist()
    return [Request(r.ts, users[r.user_id], objs[r.obj], r.tr_start, r.tr_end,
                    r.size_bytes, r.continent) for r in requests]
