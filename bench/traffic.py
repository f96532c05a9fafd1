"""The one traffic generator: a mix is a JSON file of parameters
(``bench/traffic/<mix>.json``) and this module turns it, with a seed, into
requests.

Every seed serves the same work.  The schedule (each request's arrival,
prompt and output length, and which shared prefix it uses) is drawn once
from the mix's own ``pool_seed``: a fixed trace that every run replays.
The run's seed draws the token ids (the shared prefixes' too), as it
draws the weights.  Shuffling the schedule per seed moved the
percentiles of a 51-s window by 15-60% from seed to seed while two runs
of one seed agreed within a few percent; a tail taken over some hundred
requests depends on where the bursts fall.

The lengths and prefixes are drawn for a fixed ``pool`` of requests, and
the arrival gaps from a stream of their own, so the k-th request is the
same whatever the window's length or the offered rate: a rate sweep and a
shorter window serve a prefix of the cell's own requests.

Mix keys:

* ``kind``: ``open_loop`` (requests are due at arrival times, whatever the
  server does) or ``backlog`` (a queue of at least ``backlog_per_slot`` x
  slots requests is kept full; a request is due when it is offered).
* ``arrivals`` (open loop): ``process`` ``poisson`` or ``gamma`` (with
  ``cv``, the coefficient of variation of the gaps) and ``rate_per_s``.
* ``pool``: how many requests the schedule holds (open loop: at most this
  many may arrive in ramp and window; default ``DEFAULT_POOL``).
* ``prompt`` / ``output``: ``lognormal`` with ``median``, ``sigma``, and
  the clip ``min``/``max``, in tokens.
* ``shared_prefix`` (optional): ``count`` system prompts of ``tokens``
  tokens each; every prompt is one of them plus its own ``prompt`` part.
* ``ramp_s``: seconds of the same traffic served before the window opens.
* ``trace_at_s`` (optional): where a traced run's profiled span starts,
  in seconds after the window opens; by default it is the window's end.
* ``limits``: the TTFT and time-between-tokens limits (ms) a request has
  to meet; read by the rate sweep.
"""
from __future__ import annotations

import json
import math
from dataclasses import dataclass
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


@dataclass
class Spec:
    """One request as the generator offers it."""
    idx: int
    due: float            # seconds after the ramp starts (open loop)
    prompt: np.ndarray    # int32 token ids
    max_new: int
    prefix: int           # index of its shared prefix, -1 without one
    shared: int           # tokens of the shared prefix at its head


def load(name: str, root: Path = TRAFFIC_DIR) -> dict:
    return json.loads((Path(root) / f"{name}.json").read_text())


def _lengths(rng, spec: dict, n: int) -> np.ndarray:
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    x = rng.lognormal(math.log(spec["median"]), spec["sigma"], n)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)


def _gaps(rng, arr: dict, n: int) -> np.ndarray:
    mean = 1.0 / arr["rate_per_s"]
    if arr["process"] == "poisson":
        return rng.exponential(mean, n)
    if arr["process"] == "gamma":
        shape = 1.0 / arr["cv"] ** 2
        return rng.gamma(shape, mean / shape, n)
    raise ValueError(f"unknown arrival process {arr['process']!r}")


DEFAULT_POOL = 4096


def generate(mix: dict, seed: int, *, seconds: float,
             vocab: int, rate: float | None = None) -> list[Spec]:
    """The run's requests in due order.  An open loop offers the arrivals
    that the mix's gaps fit into ramp and window; a backlog offers the
    whole ``pool``.  ``rate`` overrides the mix's ``rate_per_s`` (the
    sweep's offered rates): the gaps are then the same draws scaled, so
    two rates differ only in the clock, and serve the same requests."""
    seed0 = int(mix["pool_seed"])
    pool = int(mix.get("pool", DEFAULT_POOL))
    base = np.random.default_rng(seed0)
    plen = _lengths(base, mix["prompt"], pool)
    olen = _lengths(base, mix["output"], pool)
    sp = mix.get("shared_prefix")
    pfx = base.integers(0, sp["count"], pool) if sp else np.full(pool, -1)
    gaps = None
    if mix["kind"] == "open_loop":
        arr = mix["arrivals"]
        span = mix["ramp_s"] + seconds
        fastest = max(arr["rate_per_s"], rate or 0.0)
        # a stream of its own, whose first draws do not depend on how
        # many are drawn
        gaps = _gaps(np.random.default_rng([seed0, 1]), arr,
                     int(math.ceil(span * fastest * 2)) + 64)
        if rate is not None:
            gaps = gaps * (arr["rate_per_s"] / rate)
        n = int(np.searchsorted(np.cumsum(gaps), span, side="right"))
        if n > pool:
            raise ValueError(f"{n} arrivals in {span} s exceed the mix's "
                             f"pool of {pool}")
        gaps = gaps[:n]
    elif mix["kind"] == "backlog":
        n = pool
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")

    due = np.zeros(n) if gaps is None \
        else np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    rng = np.random.default_rng(int(seed) & (2**64 - 1))
    systems = [rng.integers(0, vocab, sp["tokens"], dtype=np.int32)
               for _ in range(sp["count"])] if sp else []
    out = []
    for i in range(n):
        own = rng.integers(0, vocab, int(plen[i]), dtype=np.int32)
        prompt = np.concatenate([systems[pfx[i]], own]) if pfx[i] >= 0 \
            else own
        out.append(Spec(i, float(due[i]), prompt, int(olen[i]), int(pfx[i]),
                        sp["tokens"] if pfx[i] >= 0 else 0))
    return out

