"""The one traffic generator: reads a mix's parameters from
``bench/traffic/<mix>.json`` and turns them into a query order and arrival
times for one run.

A mix file holds:

- ``arrivals``: ``"at_once"`` (every query of the stream is due at t=0)
  or ``"poisson"`` (open loop at ``rate_qps``);
- ``rate_qps``: the offered rate of an open-loop stream;
- ``multiple``: for ``at_once``, the stream length is rounded up to a
  multiple of this (whole flushes);
- ``warmup_queries`` / ``warmup_seconds``: the warm-up stream run during
  set-up (``at_once`` sizes its window from the rate that stream reached);
- ``topology``: ``TopologyConfig`` fields this mix sets over the
  configuration's own.

Queries come from the configuration's pool in a seeded order, cycled.
Open-loop streams are given a fixed count, ``round(rate * seconds)``, with
arrival times drawn as sorted uniforms over the window: a Poisson process
conditioned on its count, so every seed offers the same amount of work.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

import numpy as np

__all__ = ["load_mix", "stream", "warmup_stream"]

KINDS = ("at_once", "poisson")


def load_mix(path: Path) -> dict:
    mix = json.loads(Path(path).read_text())
    if mix.get("arrivals") not in KINDS:
        raise ValueError(f"{path}: arrivals must be one of {KINDS}, got "
                         f"{mix.get('arrivals')!r}")
    if mix["arrivals"] != "at_once" and not mix.get("rate_qps", 0) > 0:
        raise ValueError(f"{path}: an open-loop mix needs rate_qps > 0")
    return mix


def _rng(seed: int, tag: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), tag])


def _order(rng: np.random.Generator, pool: int, n: int) -> np.ndarray:
    """``n`` pool indices: seeded permutations of the pool, cycled."""
    reps = -(-n // pool)
    return np.concatenate([rng.permutation(pool) for _ in range(reps)])[:n]


def _times(mix: dict, rng: np.random.Generator, n: int,
           seconds: float) -> np.ndarray:
    if mix["arrivals"] == "at_once":
        return np.zeros(n)
    return np.sort(rng.uniform(0.0, seconds, n))


def _count(mix: dict, seconds: float, rate_hint: float | None) -> int:
    if mix["arrivals"] == "at_once":
        if not rate_hint or rate_hint <= 0:
            raise ValueError("an at_once window is sized from the rate the "
                             "warm-up reached; none was given")
        m = int(mix.get("multiple", 1))
        return max(m, int(math.ceil(rate_hint * seconds / m)) * m)
    return max(1, int(round(float(mix["rate_qps"]) * seconds)))


def stream(mix: dict, pool: int, seed: int, seconds: float,
           rate_hint: float | None = None) -> tuple[np.ndarray, np.ndarray]:
    """(pool indices, arrival seconds) of the measured window."""
    n = _count(mix, seconds, rate_hint)
    return (_order(_rng(seed, 1), pool, n),
            _times(mix, _rng(seed, 2), n, seconds))


def warmup_stream(mix: dict, pool: int, seed: int
                  ) -> tuple[np.ndarray, np.ndarray]:
    """(pool indices, arrival seconds) of the set-up's warm-up stream."""
    if mix["arrivals"] == "at_once":
        n = int(mix.get("warmup_queries", 512))
        return _order(_rng(seed, 3), pool, n), np.zeros(n)
    seconds = float(mix.get("warmup_seconds", 1.0))
    n = _count(mix, seconds, None)
    return (_order(_rng(seed, 3), pool, n),
            _times(mix, _rng(seed, 4), n, seconds))
