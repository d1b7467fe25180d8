"""Seeded traffic from a mix's data file: one general generator.

Every length and every gap between arrivals is a STRATIFIED sample: the
file names a distribution and a count, the generator cuts the distribution
into that many equal-mass strata and takes the value at each stratum's
middle. Every seed therefore sends the same multiset of prompt lengths,
output lengths and gaps (PR 22's refusal: a rate that moved with which
lengths the seed drew), and sends it in the same order, which the file's
``order_seed`` fixes: the run's seed decides the token ids (and the
weights), nothing else. PR 25 measured why: with the order drawn from the
run's seed, two runs of one seed agreed within 0.9% and two seeds differed
by up to 4.5%, because the order of lengths sets which decode-block sizes
the engine's ladder picks. The order is part of the traffic, not noise.

The sample is also stratified in GROUPS: with ``count = groups x group``,
group ``j`` holds one value from each of ``group`` coarse strata, so any
run of ``group`` consecutive requests carries nearly the whole
distribution. ``order_seed`` permutes within groups and the order of groups.

No jax here: the program receives only the generated inputs.
"""

from __future__ import annotations

import dataclasses
import json
import math
import os

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
KINDS = ("backlog", "open-loop", "train")


class TrafficError(ValueError):
    """A traffic file the generator cannot read."""


def load_mix(name: str, root: str = HERE) -> dict:
    """The mix ``name`` from ``<root>/traffic/<name>.json``."""
    path = os.path.join(root, "traffic", f"{name}.json")
    with open(path) as f:
        mix = json.load(f)
    if mix.get("kind") not in KINDS:
        raise TrafficError(f"{path}: kind must be one of {KINDS}")
    return mix


# -- distributions: quantile functions on (0, 1) -----------------------------


def quantile(dist: dict, u: np.ndarray) -> np.ndarray:
    """The distribution's value at mass ``u``; lengths are rounded by the
    caller."""
    kind = dist["dist"]
    if kind == "uniform":
        return dist["lo"] + (dist["hi"] - dist["lo"]) * u
    if kind == "loguniform":
        lo, hi = math.log(dist["lo"]), math.log(dist["hi"])
        return np.exp(lo + (hi - lo) * u)
    if kind == "exponential":
        return -dist["mean"] * np.log1p(-u)
    if kind == "gamma":
        # no closed quantile: invert a fine table of the density's integral
        k, mean = dist["shape"], dist["mean"]
        grid = np.linspace(0.0, 40.0 * max(k, 1.0), 400_001)[1:]
        pdf = grid ** (k - 1.0) * np.exp(-grid)
        cdf = np.cumsum(pdf)
        cdf /= cdf[-1]
        return np.interp(u, cdf, grid) * (mean / k)
    if kind == "fixed":
        return np.full_like(u, dist["value"], dtype=float)
    raise TrafficError(f"unknown distribution {kind!r}")


def strata(dist: dict, count: int, group: int) -> np.ndarray:
    """``count`` values, one from each of ``count`` equal-mass strata, as
    a ``(groups, group)`` table: row ``j`` takes coarse stratum ``i`` at
    the fine offset ``j``, so each row is itself a stratified sample."""
    if count < 1 or group < 1 or count % group:
        raise TrafficError(
            f"count ({count}) must be a positive multiple of group ({group})")
    groups = count // group
    i = np.arange(group)[None, :]
    j = np.arange(groups)[:, None]
    u = (i + (j + 0.5) / groups) / group
    return quantile(dist, u)


def _shuffled(table: np.ndarray, rng: np.random.Generator) -> np.ndarray:
    """Rows in a seeded order, each row's entries in a seeded order."""
    rows = rng.permutation(table.shape[0])
    return np.stack([rng.permutation(table[r]) for r in rows])


def lengths(dist: dict, count: int, group: int,
            rng: np.random.Generator) -> np.ndarray:
    """Integer lengths: the stratified multiset in the seed's order. A
    ``multiple_of`` in the file rounds every length to that grid (the
    program compiles small pool writes anew for every distinct prompt
    length, so a mix keeps the number of distinct lengths small)."""
    step = int(dist.get("multiple_of", 1))
    table = np.rint(strata(dist, count, group) / step).astype(np.int64) * step
    return _shuffled(table, rng).reshape(-1)


def prompt_lengths(mix: dict) -> list[int]:
    """Every prompt length the mix can send, whatever the seed and the
    run's length: the file's grid between its two ends. What set-up has
    to warm."""
    dist = mix["prompt_len"]
    if dist["dist"] == "fixed":
        return [int(dist["value"])]
    step = int(dist.get("multiple_of", 1))
    lo = int(np.rint(dist["lo"] / step)) * step
    hi = int(np.rint(dist["hi"] / step)) * step
    return list(range(lo, hi + 1, step))


# -- requests ----------------------------------------------------------------


@dataclasses.dataclass
class Request:
    prompt: np.ndarray   # (P,) int32 token ids
    max_new: int
    due: float | None = None   # seconds after the generator's zero (open loop)
    timed: bool = True         # False: sent before the window, to fill it


def _requests(mix: dict, vocab: int, count: int, rng: np.random.Generator,
              order: np.random.Generator) -> list[Request]:
    """Lengths in the file's order, token ids from the run's seed."""
    group = int(mix["group"])
    p = lengths(mix["prompt_len"], count, group, order)
    o = lengths(mix["output_len"], count, group, order)
    return [Request(rng.integers(0, vocab, size=int(pl), dtype=np.int64)
                    .astype(np.int32), int(ol)) for pl, ol in zip(p, o)]


def _rng(seed: int, salt: int) -> np.random.Generator:
    return np.random.default_rng([int(seed), salt])


def backlog_requests(mix: dict, vocab: int, seed: int) -> list[Request]:
    """``count`` requests in the seed's order; the runner cycles through
    them for as long as the run lasts."""
    return _requests(mix, vocab, int(mix["count"]), _rng(seed, 0x7AFF1C),
                     _rng(mix["order_seed"], 0x7AFF1C))


def first_slotful_budgets(reqs: list[Request], slots: int) -> list[int]:
    """Output budgets for the first ``slots`` requests of a backlog, cut
    to a stratified share of their length (one share from each of
    ``slots`` equal strata of (0, 1]) so that the slots are at mixed
    phases from the first block on. The order of shares follows the
    request order, which the seed already permuted."""
    shares = (np.arange(slots) + 1.0) / slots
    return [max(1, int(round(r.max_new * s)))
            for r, s in zip(reqs[:slots], shares)]


def open_loop_requests(mix: dict, vocab: int, seed: int,
                       seconds: float) -> list[Request]:
    """Arrivals on the wall clock at the file's rate: first ``fill_s``
    seconds of them to fill the engine (``timed`` False), then the
    window's. Each part is a stratified sample of its own, of as many
    whole groups as its length holds at the rate, with gaps that sum to
    its length exactly: every seed sends the same requests' lengths at
    the same instants, with other token ids."""
    rate, group = float(mix["rate_per_s"]), int(mix["group"])
    out: list[Request] = []
    offset = 0.0
    for salt, span, timed in ((0xF111, float(mix["fill_s"]), False),
                              (0x7AB1E, float(seconds), True)):
        order = _rng(mix["order_seed"], salt)
        count = group * max(1, round(span * rate / group))
        reqs = _requests(mix, vocab, count, _rng(seed, salt), order)
        gap = dict(mix["gap"], mean=1.0)
        gaps = _shuffled(strata(gap, count, group), order).reshape(-1)
        gaps *= span / gaps.sum()
        due = offset + np.cumsum(gaps) - gaps
        for r, d in zip(reqs, due):
            r.due, r.timed = float(d), timed
        out += reqs
        offset += span
    return out


def train_batches(vocab: int, seed: int, rows: int,
                  seq: int) -> tuple[np.ndarray, np.ndarray]:
    """``rows`` fresh sequences of ``seq + 1`` seeded token ids, as inputs
    and next-token labels; every row differs."""
    rng = _rng(seed, 0x7A11)
    tokens = rng.integers(0, vocab, size=(rows, seq + 1), dtype=np.int64)
    tokens = tokens.astype(np.int32)
    return tokens[:, :-1], tokens[:, 1:]
