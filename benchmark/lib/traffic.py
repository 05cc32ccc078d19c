"""The one traffic generator: a traffic file's parameters and ``--seed``
in, the requests or batches of one run out.

Every seed gets the same work.  The request sizes, the gaps between
arrivals and the order of both are drawn once from the file's
``base_seed`` and the run's length, so every run of a cell replays ONE
sequence; ``--seed`` fills in the token ids (and the weights).  A window
holds tens of requests, and their order alone moved ``ttft_p95_ms`` by
half between seeds (chip, PR 25), so a tail here is the tail of that one
sequence.  Sizes are drawn stratified (one from each equal slice of the
distribution) and dealt into blocks that each span the whole
distribution, so any stretch of a run sees the same mix.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from typing import List

import numpy as np

SEED_MOD = 2 ** 32          # --seed may be a little over 2**31


def _rng(*parts: int) -> np.random.Generator:
    return np.random.default_rng([int(p) % SEED_MOD for p in parts])


def stratified(spec: dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``n`` whole numbers log-uniform on ``{"min": a, "max": b}``
    (uniform in the logarithm, both ends included), one from each of
    ``n`` equal slices of the distribution, in rising order."""
    lo, hi = float(spec["min"]), float(spec["max"])
    if not 0 < lo <= hi:
        raise ValueError(f"bad range in {spec}")
    u = (np.arange(n) + rng.random(n)) / n
    x = np.exp(math.log(lo) + u * (math.log(hi) - math.log(lo)))
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def block_order(n: int, block: int, rng: np.random.Generator) -> np.ndarray:
    """An order of ``range(n)`` (taken as sorted by size) in which every
    run of ``block`` consecutive places holds one item from each of
    ``block`` equal slices of the sorted range: item ``j`` of slice ``s``
    goes to block ``j``; the order inside a block and the order of the
    blocks are the rng's."""
    block = max(1, min(block, n))
    slices = np.array_split(np.arange(n), block)
    for s in slices:
        rng.shuffle(s)
    n_blocks = max(len(s) for s in slices)
    blocks = []
    for j in rng.permutation(n_blocks):
        members = np.array([s[j] for s in slices if j < len(s)])
        rng.shuffle(members)
        blocks.append(members)
    return np.concatenate(blocks)


@dataclass
class Request:
    prompt: List[int]
    max_new_tokens: int


@dataclass
class ServePlan:
    """One run's requests.  Open loop: ``due_s[i]`` is when request ``i``
    is due, from the window's start.  Closed loop: ``due_s`` is None and
    ``clients[c]`` lists the indices client ``c`` sends, one after the
    other."""
    requests: List[Request]
    due_s: np.ndarray | None
    clients: List[List[int]] | None


def request_sizes(traffic: dict, n: int) -> tuple[np.ndarray, np.ndarray]:
    """The fixed set of (prompt, answer) sizes of a cell, sorted by prompt
    size: the same for every seed."""
    base = _rng(traffic["base_seed"], n)
    prompts = stratified(traffic["prompt_tokens"], n, base)
    answers = stratified(traffic["answer_tokens"], n, base)
    if not traffic["answer_follows_prompt"]:
        answers = answers[base.permutation(n)]
    return prompts, answers


def n_requests(traffic: dict, seconds: float) -> int:
    if traffic["driver"] == "open_loop":
        return max(1, int(round(traffic["rate_per_s"] * seconds)))
    return int(math.ceil(traffic["requests_per_s_ceiling"] * seconds))


def serve_plan(traffic: dict, seed: int, seconds: float,
               vocab_size: int) -> ServePlan:
    n = n_requests(traffic, seconds)
    prompts, answers = request_sizes(traffic, n)
    rng = _rng(traffic["base_seed"], 0)       # the order: not --seed's
    order = block_order(n, int(traffic["block"]), rng)
    ids_rng = _rng(traffic["base_seed"], seed, 2)
    requests = [Request(ids_rng.integers(0, vocab_size,
                                         size=int(prompts[i])).tolist(),
                        int(answers[i])) for i in order]
    if traffic["driver"] == "open_loop":
        # Poisson arrivals: exponential gaps, drawn as a gamma of shape 1
        # because the sequence PR 25 measured came from that call
        gaps = _rng(traffic["base_seed"], n, 1).gamma(1.0, 1.0, n)
        # the same n gaps every run, scaled so the last arrival falls
        # half a mean gap before the window's end
        gaps *= (seconds * (n - 0.5) / n) / gaps.sum()
        gaps = gaps[rng.permutation(n)]
        due = np.cumsum(gaps) - gaps[0]
        return ServePlan(requests, due, None)
    c = int(traffic["clients"])
    return ServePlan(requests, None, [list(range(j, n, c)) for j in range(c)])


def train_batch(traffic: dict, seed: int, step: int, rows: int,
                vocab_size: int) -> dict:
    """Step ``step``'s batch of a training run: ``rows`` sequences of
    ``seq_len`` random tokens, labels the inputs shifted by one."""
    seq = int(traffic["seq_len"])
    ids = _rng(traffic["base_seed"], seed, step).integers(
        0, vocab_size, size=(rows, seq + 1), dtype=np.int32)
    return {"input_ids": ids[:, :-1], "labels": ids[:, 1:].copy()}


def max_context(traffic: dict) -> int:
    """The longest prompt plus answer the traffic can ask for."""
    return int(traffic["prompt_tokens"]["max"]
               + traffic["answer_tokens"]["max"])
