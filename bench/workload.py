"""Traffic generation: one general generator that reads a mix file.

A mix file (``bench/traffic/<mix>.json``) holds parameters only. This module
turns it, with ``--seed``, into the requests of one run. Every seed offers the
same work at the same times: sizes and inter-arrival gaps are the
distribution's quantiles at ``(i + 0.5) / n``, put in an order that is the
mix's own, and DLRM bag lengths are one draw that is the mix's own too. The
seed draws what a request holds: token ids, embedding ids, dense features.
An order drawn from the seed would let bursts fall on other requests in each
run and move the queueing tails, and the DLRM ingest compiles (shaped by bag
lengths), from seed to seed.

Keys a mix may hold:

- ``loop``: ``"open"`` (a schedule of due times, sent whatever the server
  does) or ``"closed"`` (``callers`` clients, each sending its next request
  when the previous one is answered).
- ``arrivals`` (open loop): ``{"process": "poisson", "rate_per_s": r}`` or
  ``{"process": "gamma", "rate_per_s": r, "cv": c}`` (a renewal process whose
  gaps have coefficient of variation ``c``; ``c > 1`` is bursty).
- LM: ``prompt_tokens`` and ``output_tokens``, each a length distribution:
  ``{"dist": "lognormal", "median": m, "sigma": s, "min": a, "max": b}``,
  ``{"dist": "uniform", "min": a, "max": b}`` or ``{"dist": "fixed",
  "value": v}``. ``prefix`` (optional): ``{"groups": g, "tokens": <length
  distribution>}`` gives every request one of ``g`` shared prefixes, drawn
  uniformly, ahead of its own prompt.
- DLRM: ``items_per_request``, ``bags`` (``{"dist": "poisson_plus_one"}``
  with the configuration's per-table means, clipped to its bag limit) and
  ``ids`` (``{"dist": "zipf", "alpha": a}`` or ``{"dist": "uniform"}``).
"""
from __future__ import annotations

import json
from dataclasses import dataclass
from pathlib import Path
from typing import List

import numpy as np
from scipy import stats


def load(path) -> dict:
    with open(path) as f:
        return json.load(f)


def rng(seed: int, *stream: int) -> np.random.Generator:
    """An independent stream per purpose; any whole-number seed works."""
    return np.random.default_rng([int(seed) % (1 << 64), *stream])


# streams: what each generator draws from
ORDER, TOKENS, PREFIX, CHECK, IDS, WARM = 1, 2, 3, 4, 5, 6
SCHEDULE = 0        # the seed that orders a mix's sizes and gaps, and draws
                    # its bag lengths: the same in every run


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The distribution's quantiles at (i + 0.5) / n, ascending, as ints
    clipped to [min, max]."""
    u = (np.arange(n) + 0.5) / n
    d = spec["dist"]
    if d == "fixed":
        v = np.full(n, float(spec["value"]))
    elif d == "uniform":
        lo, hi = spec["min"], spec["max"]
        v = lo + np.floor(u * (hi - lo + 1))
    elif d == "lognormal":
        v = spec["median"] * np.exp(spec["sigma"] * stats.norm.ppf(u))
    else:
        raise ValueError(f"unknown length distribution {d!r}")
    lo, hi = spec.get("min", 1), spec.get("max", np.inf)
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def gaps(arrivals: dict, n: int) -> np.ndarray:
    """n inter-arrival gaps (s), ascending: the quantiles of the process's
    gap distribution, rescaled so that their mean is exactly 1 / rate."""
    u = (np.arange(n) + 0.5) / n
    p = arrivals["process"]
    if p == "poisson":
        g = stats.expon.ppf(u)
    elif p == "gamma":
        k = 1.0 / arrivals["cv"] ** 2
        g = stats.gamma.ppf(u, k)
    else:
        raise ValueError(f"unknown arrival process {p!r}")
    return g / g.mean() / arrivals["rate_per_s"]


def open_loop_due(arrivals: dict, n: int) -> np.ndarray:
    """Due times (s from the opening) of n requests, the first at 0."""
    gap = rng(SCHEDULE, ORDER, 1).permutation(gaps(arrivals, n))
    return np.concatenate([[0.0], np.cumsum(gap)[:-1]])


@dataclass
class LMRequest:
    due_s: float            # offset from the opening of the window
    prompt: np.ndarray      # int32 token ids
    max_new: int


def lm_open_loop(mix: dict, seed: int, seconds: float,
                 vocab: int) -> List[LMRequest]:
    """The requests due in a window of ``seconds``: rate x seconds of them,
    the first due at the opening."""
    n = max(int(round(mix["arrivals"]["rate_per_s"] * seconds)), 1)
    due = open_loop_due(mix["arrivals"], n)
    r = rng(SCHEDULE, ORDER)
    plen = r.permutation(quantiles(mix["prompt_tokens"], n))
    olen = r.permutation(quantiles(mix["output_tokens"], n))
    tok = rng(seed, TOKENS)
    pre = []
    if mix.get("prefix"):
        g = mix["prefix"]["groups"]
        pr = rng(SCHEDULE, PREFIX)
        plens = pr.permutation(quantiles(mix["prefix"]["tokens"], g))
        which = pr.integers(0, g, n)
        pc = rng(seed, PREFIX)
        pre = [pc.integers(0, vocab, int(k)).astype(np.int32) for k in plens]
    out = []
    for i in range(n):
        p = tok.integers(0, vocab, int(plen[i])).astype(np.int32)
        if pre:
            p = np.concatenate([pre[which[i]], p])
        out.append(LMRequest(float(due[i]), p, int(olen[i])))
    return out


def dlrm_request(mix: dict, cfg: dict, seed: int, index: int,
                 stream: int = IDS) -> dict:
    """Request ``index`` of a run: ``items_per_request`` samples, each with
    dense features and one bag of ids per table (padded to the bag limit).
    Warm-up requests come from a stream of their own (``WARM``)."""
    r = rng(seed, stream, index)
    B = mix["items_per_request"]
    rows = np.asarray(cfg["table_rows"])
    means = np.asarray(cfg["avg_lookups_per_table"])
    L = cfg["max_lookups_per_table"]
    T = len(rows)
    if mix["bags"]["dist"] != "poisson_plus_one":
        raise ValueError(f"unknown bag distribution {mix['bags']['dist']!r}")
    dense = r.normal(size=(B, cfg["num_dense_features"])).astype(np.float32)
    lengths = np.minimum(
        rng(SCHEDULE, stream, index).poisson(means[None, :], (B, T)) + 1,
        L).astype(np.int32)
    ids = mix["ids"]
    indices = np.empty((B, T, L), np.int32)
    for t in range(T):
        if ids["dist"] == "zipf":
            raw = r.zipf(ids["alpha"], size=(B, L))
            indices[:, t] = np.minimum(raw - 1, rows[t] - 1)
        elif ids["dist"] == "uniform":
            indices[:, t] = r.integers(0, rows[t], (B, L))
        else:
            raise ValueError(f"unknown id distribution {ids['dist']!r}")
    # ids past a bag's length are padding the engine masks; zero them so
    # the padding reads the same row whatever the seed
    indices[np.arange(L)[None, None, :] >= lengths[..., None]] = 0
    return {"dense": dense, "indices": indices, "lengths": lengths}


def find(root: Path, kind: str, name: str) -> Path:
    """``<root>/bench/<kind>/<name>.json``: mixes are found by name."""
    p = Path(root) / "bench" / kind / f"{name}.json"
    if not p.is_file():
        raise FileNotFoundError(f"no {kind} file {p}")
    return p
