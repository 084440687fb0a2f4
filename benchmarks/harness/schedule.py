"""The one traffic generator: a data file of parameters in, requests out.

Two things are drawn from two different seeds, on purpose.  WHEN requests
arrive and HOW LONG they are comes from the traffic file's own
``schedule_seed`` and is identical in every run of a cell: PR 22's latency
cell drew a new schedule per run and its mean TTFT spread 25% between runs
of one program, because at 50-200 requests a window the realised schedule IS
the result.  WHAT the tokens are comes from ``--seed``.

Lengths are not sampled: for N requests they are the N quantile midpoints
of the stated distribution, permuted.  The offered work of a window is then
exact (the same multiset whatever the permutation), not a draw.
"""

from __future__ import annotations

import json
from dataclasses import asdict, dataclass
from statistics import NormalDist

import numpy as np


@dataclass(frozen=True)
class Arrival:
    due_s: float        # seconds from the start of the replay
    prompt_len: int
    output_len: int
    counted: bool       # due inside the measured window


def quantile_lengths(spec: dict, n: int) -> list:
    """The n quantile midpoints (i + 0.5) / n of the distribution in
    ``spec``, clipped to [min, max], as whole numbers, ascending."""
    if spec.get("dist", "lognormal") != "lognormal":
        raise ValueError(f"unknown length distribution {spec.get('dist')!r}")
    nd = NormalDist()
    out = []
    for i in range(n):
        z = nd.inv_cdf((i + 0.5) / n)
        x = float(spec["median"]) * float(np.exp(float(spec["sigma"]) * z))
        out.append(int(min(max(round(x), int(spec["min"])), int(spec["max"]))))
    return out


def _rng(schedule_seed: int, *stream) -> np.random.Generator:
    return np.random.default_rng([int(schedule_seed), *map(int, stream)])


def permuted_lengths(traffic: dict, n: int, phase: int) -> list:
    """[(prompt_len, output_len)] * n: each distribution's quantile
    midpoints under its own permutation."""
    seed = traffic["schedule_seed"]
    p = quantile_lengths(traffic["prompt_len"], n)
    o = quantile_lengths(traffic["output_len"], n)
    p = [p[i] for i in _rng(seed, phase, 1).permutation(n)]
    o = [o[i] for i in _rng(seed, phase, 2).permutation(n)]
    return list(zip(p, o))


def open_loop_schedule(traffic: dict, seconds: float, *,
                       rate_rps: float = None) -> list:
    """Arrivals of an ``open_loop_schedule`` traffic file for a measured
    window of ``seconds``: a warm-up stretch (sent, not counted), the
    window, and a tail at the same rate that keeps the system loaded while
    the counted requests drain (sent while needed, not counted).

    Pacing is jittered, not Poisson: one arrival in each interval of
    1/rate, at a uniform offset inside it."""
    if traffic.get("arrivals", "jittered") != "jittered":
        raise ValueError(f"unknown arrivals {traffic.get('arrivals')!r}")
    rate = float(traffic["rate_rps"] if rate_rps is None else rate_rps)
    seed = traffic["schedule_seed"]
    phases = ((float(traffic["warmup_s"]), False), (float(seconds), True),
              (float(traffic["drain_timeout_s"]), False))
    out, start = [], 0.0
    for phase, (length, counted) in enumerate(phases):
        n = int(round(rate * length))
        offsets = _rng(seed, phase, 0).random(n)
        for i, (p, o) in enumerate(permuted_lengths(traffic, n, phase)):
            out.append(Arrival(start + (i + float(offsets[i])) / rate, p, o,
                               counted))
        start += length
    return out


def backlog_lengths(traffic: dict) -> list:
    """The fixed pool of (prompt_len, output_len) a ``backlog`` cell cycles
    through, in its fixed order."""
    return permuted_lengths(traffic, int(traffic["pool_requests"]), 0)


def token_ids(seed: int, lengths, id_range) -> list:
    """One list of ids per prompt length, uniform over ``id_range`` (low,
    high: the ids the architecture's adapter says traffic draws from), from
    ``--seed``: the only thing of a schedule that a seed changes."""
    rng = np.random.default_rng(int(seed))
    low, high = id_range
    return [rng.integers(low, high, int(n)).astype(np.int32).tolist()
            for n in lengths]


def schedule_bytes(schedule) -> bytes:
    """Canonical serialisation, for comparing two schedules exactly."""
    return json.dumps([asdict(a) for a in schedule],
                      sort_keys=True).encode()


def reach(traffic: dict) -> dict:
    """The longest prompt and the longest prompt + answer a traffic file
    can produce: what set-up has to warm."""
    p, o = traffic["prompt_len"], traffic["output_len"]
    return {"max_prompt": int(p["max"]),
            "max_total": int(p["max"]) + int(o["max"])}
