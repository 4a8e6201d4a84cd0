"""The one general traffic generator: every mix is a data file it reads.

A mix under ``benchmark/traffic/<name>.json`` states lengths, rates and
arrival blocks as parameters; every draw comes from ``--seed``, so
the same seed gives the same batches, prompts, output lengths and arrival
times. Nothing here touches JAX: the program under test receives only the
generated inputs.

Length specs: ``{"dist": "uniform", "lo": a, "hi": b}`` and ``{"dist":
"loguniform", "lo": a, "hi": b}`` (inclusive bounds; ``"stratified": true``
in the mix draws them by strata, see ``draw_lengths``). Token spec:
``{"dist": "zipf", "a": 1.1}``, always over the PUBLISHED vocabulary (the
padded rows are never drawn). Arrival spec of an open loop: ``{"process":
"fixed_count", "block_s": s}``: ``rate_rps`` x s arrivals at independent
uniform times in every block of s seconds. That is a Poisson process GIVEN
its count in each block, not a Poisson process: every seed offers the same
load, and the swings in load that build a queue are held inside a block.
"""
from __future__ import annotations

from typing import Dict, List

import numpy as np


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """An independent stream per purpose, so that adding a draw to one
    (say, prompts) never shifts another (arrivals)."""
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed), tag])


def draw_lengths(spec: dict, rng: np.random.Generator, n: int,
                 stratified: bool = False) -> np.ndarray:
    """``n`` lengths from the distribution. ``stratified`` draws one
    length from each of n equal-probability strata, in random order: the
    same distribution request by request, but the TOTAL work of a plan
    hardly varies with the seed (a plain draw of 48 answers varies by 8%
    in total tokens, which moves an open loop's queue more than most
    changes to the program would)."""
    dist = spec["dist"]
    lo, hi = int(spec["lo"]), int(spec["hi"])
    u = rng.uniform(size=n)
    if stratified:
        u = rng.permutation((np.arange(n) + u) / n)
    if dist == "uniform":
        return np.clip(np.floor(lo + u * (hi + 1 - lo)).astype(np.int64), lo, hi)
    if dist == "loguniform":
        x = np.exp(np.log(lo) + u * (np.log(hi + 1) - np.log(lo)))
        return np.clip(np.floor(x).astype(np.int64), lo, hi)
    raise ValueError(f"unknown length distribution {dist!r}")


def draw_tokens(spec: dict, rng: np.random.Generator, shape, vocab: int) -> np.ndarray:
    """Token ids in [0, vocab). Zipf ranks are scattered over the
    vocabulary by a seed-independent fixed permutation multiplier so hot
    tokens are not all in the first embedding rows."""
    if spec["dist"] != "zipf":
        raise ValueError(f"unknown token distribution {spec['dist']!r}")
    ranks = (rng.zipf(float(spec["a"]), size=shape) - 1) % vocab
    return ((ranks * 7919) % vocab).astype(np.int32)  # 7919 is prime, coprime to vocab


def train_batches(traffic: dict, vocab: int, seed: int) -> List[Dict[str, np.ndarray]]:
    """``n_host_batches`` distinct (tokens, labels) host batches; labels
    are the next tokens of the same drawn stream."""
    rng = rng_for(seed, "train")
    B, T = int(traffic["global_batch"]), int(traffic["seq"])
    out = []
    for _ in range(int(traffic["n_host_batches"])):
        stream = draw_tokens(traffic["tokens"], rng, (B, T + 1), vocab)
        out.append({"tokens": np.ascontiguousarray(stream[:, :-1]).astype(np.int64),
                    "labels": np.ascontiguousarray(stream[:, 1:]).astype(np.int64)})
    return out


def _requests(traffic: dict, vocab: int, rng: np.random.Generator, n: int,
              groups=None) -> List[dict]:
    """``n`` requests; with ``stratified`` the lengths are stratified inside
    each of ``groups`` (consecutive counts summing to n; default one group)."""
    strat = bool(traffic.get("stratified", False))
    sizes = [int(g) for g in (groups if groups is not None else [n]) if g]
    p_len = np.concatenate([draw_lengths(traffic["prompt_len"], rng, g, strat) for g in sizes]
                           or [np.zeros(0, np.int64)])
    o_len = np.concatenate([draw_lengths(traffic["output_len"], rng, g, strat) for g in sizes]
                           or [np.zeros(0, np.int64)])
    max_total = int(traffic["max_total"])
    # an answer never runs the context past the model's positions
    o_len = np.minimum(o_len, np.maximum(1, max_total - p_len))
    reqs = []
    for i in range(n):
        toks = draw_tokens(traffic["tokens"], rng, (int(p_len[i]),), vocab)
        reqs.append({"prompt": toks.tolist(), "max_new_tokens": int(o_len[i])})
    return reqs


def closed_loop_plan(traffic: dict, vocab: int, seed: int, per_client: int) -> List[List[dict]]:
    """For each client its own fixed sequence of requests. The first
    answer of each client is cut to a uniform share of its drawn length
    (the residual life of a request met in steady state), so the clients
    do not all finish together."""
    n_clients = int(traffic["clients"])
    plans = []
    for c in range(n_clients):
        rng = rng_for(seed, f"c{c}")
        reqs = _requests(traffic, vocab, rng, per_client)
        share = rng.uniform(0.05, 1.0)
        reqs[0]["max_new_tokens"] = max(1, int(reqs[0]["max_new_tokens"] * share))
        plans.append(reqs)
    return plans


def arrival_times(traffic: dict, seed: int, horizon_s: float) -> np.ndarray:
    """Due times in [0, horizon) of an open loop at ``rate_rps``: the same
    number of arrivals in every block, at independent uniform times inside
    it, so every seed offers the same load."""
    rng = rng_for(seed, "arrive")
    rate = float(traffic["rate_rps"])
    spec = traffic["arrivals"]
    if spec["process"] != "fixed_count":
        raise ValueError(f"unknown arrival process {spec['process']!r}")
    block = float(spec["block_s"])
    out, t0, owed = [], 0.0, 0.0
    while t0 < horizon_s:
        length = min(block, horizon_s - t0)
        owed += rate * length
        k = int(owed + 0.5)
        owed -= k
        out.append(t0 + np.sort(rng.uniform(0.0, length, size=k)))
        t0 += block
    return np.concatenate(out)


def open_loop_plan(traffic: dict, vocab: int, seed: int, horizon_s: float) -> List[dict]:
    due = arrival_times(traffic, seed, horizon_s)
    # lengths stratified inside the same blocks the counts are fixed in
    block = float(traffic["arrivals"]["block_s"])
    groups = np.bincount((due // block).astype(np.int64)).tolist()
    reqs = _requests(traffic, vocab, rng_for(seed, "open"), len(due), groups)
    for r, t in zip(reqs, due):
        r["due_s"] = float(t)
    return reqs
