"""The general traffic generator: a pure function of (traffic file, cell file,
configuration, seed, seconds) -> every request the run will send.

One generator covers the mixes the benchmark has: open-loop Poisson arrivals,
or a closed loop of N clients; prompts that are a document (drawn
from a population that is asked about again) plus an unshared tail, or a tail
alone; every length from a weighted grid.  A mix is a data file under
``benchmarks/traffic/``; this file is not edited to add one.  (A mix this
generator cannot express, multi-turn sessions say, is a new module beside it,
named by the traffic file's ``generator`` key.)

Steadiness: every seed sends the same sizes at the same arrival times.
Lengths are apportioned exactly over blocks of ``block`` requests (largest
remainder), gaps are the stratified quantiles of the arrival distribution,
and their order is shuffled once, from the mix's own ``order_seed``.  The
run's ``--seed`` draws every token id (so which document is which, and every
store key, differs) and nothing else: two seeds differ in content, not in the
amount of work nor in how it queues.  A cell that completes some tens of
requests in a window cannot afford more: with the order left to the seed the
median over 20 requests moves by how the long ones happen to bunch.
"""

from __future__ import annotations

import itertools
import math
import random
from typing import Dict, List, Sequence

PAGE = 16


def apportion(weights: Dict[str, float], n: int) -> List[int]:
    """``n`` values of the grid ``weights`` ({value: weight}) in exact
    proportion (largest remainder, ties to the heavier then smaller value)."""
    items = sorted(((int(k), float(w)) for k, w in weights.items()))
    total = sum(w for _, w in items)
    exact = [(v, n * w / total) for v, w in items]
    counts = {v: int(math.floor(x)) for v, x in exact}
    rest = sorted(exact, key=lambda vx: (-(vx[1] - math.floor(vx[1])), -vx[1], vx[0]))
    for v, _ in rest[: n - sum(counts.values())]:
        counts[v] += 1
    return [v for v, _ in items for _ in range(counts[v])]


def gap_quantiles(n: int, rate: float) -> List[float]:
    """``n`` stratified Poisson inter-arrival gaps (exponential quantiles)
    whose mean is exactly 1/rate."""
    gaps = [-math.log(1.0 - (i + 0.5) / n) for i in range(n)]
    scale = n / sum(gaps)
    return [g * scale / rate for g in gaps]


def width_bucket(tokens: int) -> int:
    """The engine's block-table width for a sequence of ``tokens``."""
    need, width = -(-tokens // PAGE), 8
    while width < need:
        width *= 2
    return width


def body(prompt: Sequence[int], max_tokens: int, *, stream: bool = True,
         logprobs: int = 0) -> dict:
    b = {"prompt": list(prompt), "max_tokens": int(max_tokens),
         "temperature": 0, "stream": stream}
    if logprobs:
        b["logprobs"] = logprobs
    return b


def generate(traffic: dict, cell: dict, config: dict, seed: int,
             seconds: float, schedule_salt: str = "") -> dict:
    """``schedule_salt`` redraws the schedule alone (the knee sweep runs
    several windows on one population)."""
    rng = random.Random(seed)                            # contents
    order = random.Random(traffic.get("order_seed", 0))  # sizes' and gaps' order
    vocab = config["vocab_size"]
    cache_tokens = config["serve"]["n_blocks"] * config["serve"]["block_tokens"]
    max_batch = int(cell["max_batch"])
    chunk = int(traffic.get("decode_chunk", 32))
    block = int(traffic.get("block", 40))
    tails_w, outs_w = traffic["tails"], traffic["outputs"]
    docs_cfg = traffic.get("documents")
    if any(int(o) % chunk for o in outs_w):
        raise ValueError("output lengths must be multiples of decode_chunk, or "
                         "the decode scan compiles per remaining length")

    def toks(n: int) -> List[int]:
        return rng.choices(range(1, vocab), k=n)

    # -- the document population, in strata of the grid's own mix ------------
    population: List[List[int]] = []
    if docs_cfg:
        stratum = int(docs_cfg.get("stratum", 10))
        want = docs_cfg["population_cache_multiple"] * cache_tokens
        while sum(len(d) for d in population) < want:
            lens = apportion(docs_cfg["lengths"], stratum)
            order.shuffle(lens)
            population += [toks(n) for n in lens]

    # -- 1. decode warm-up: every (batch bucket, table-width bucket) ---------
    # A phase is k requests that share one base prompt (a local prefix hit,
    # so their prefill is one short chunk) and so decode together at batch k
    # and the base's width.  run.py sends a short blocker first so that the k
    # are all pending when the batch empties and are admitted as one wave.
    all_out = [int(o) for o in outs_w]
    prompt_lens = ([int(d) + int(t) for d in docs_cfg["lengths"] for t in tails_w]
                   if docs_cfg else [int(t) for t in tails_w])
    buckets = sorted({width_bucket(p + o) for p in prompt_lens for o in all_out}
                     | {width_bucket(p + chunk) for p in prompt_lens})
    batches = [1]
    while batches[-1] < max_batch:
        batches.append(min(max_batch, batches[-1] * 2))
    warm_decode = []
    for w in buckets:
        base = toks(w * PAGE // 2)
        for k in batches:
            warm_decode.append({
                "name": f"decode B={k} width={w}",
                "requests": [body(base + toks(PAGE), chunk) for _ in range(k)]})
    blocker = body(toks(PAGE), chunk)

    # -- 2. probes: fixed lengths from the traffic file, content from the seed.
    #       A re-ask probe's whole prompt is asked three times: computed, then
    #       at once again (its pages are in HBM), and after the fill, when
    #       they have left HBM and come back from the store.  run.py holds the
    #       second and third answers to each other: the same program on pages
    #       that a lossless store returns bit for bit.
    oldest = _first_of_each_length(population)   # kept for the re-ask warm-up
    probes, used = [], set(oldest)
    for p in traffic["probes"]:
        if p.get("reask"):
            j = next(i for i, d in enumerate(population[:docs_cfg.get("stratum", 10)])
                     if len(d) == p["doc"] and i not in used)
            used.add(j)
            prompt = population[j] + toks(p["tail"])
        else:
            prompt = toks(p.get("doc", 0) + p["tail"])
        probes.append({"reask": bool(p.get("reask")),
                       "body": body(prompt, 4, stream=False, logprobs=5)})
    paired = used - set(oldest)

    # -- 3. fill: every other document asked once (store filled, prefill
    #       shapes of fresh prompts warmed), enough to push the paired probes'
    #       documents out of HBM; without documents, one of each length ------
    tails = sorted(int(t) for t in tails_w)
    if docs_cfg:
        fill = [body(d + toks(tails[i % len(tails)]), chunk)
                for i, d in enumerate(population) if i not in paired]
        if paired and sum(len(b["prompt"]) for b in fill) < cache_tokens:
            raise ValueError("the fill is smaller than the HBM cache: the paired "
                             "probes' documents would never leave it")
    else:
        fill = [body(toks(t), chunk) for t in tails]

    # -- 4. re-ask warm-up: each (document length, tail) once, on the oldest
    #       document of that length (the first is a store hit) ---------------
    warm_reask = []
    if docs_cfg:
        for j in oldest:
            warm_reask += [body(population[j] + toks(t), chunk) for t in tails]

    # -- 5. the schedule: ramp + window -------------------------------------
    if schedule_salt:
        rng = random.Random(f"{seed}/{schedule_salt}")
    ramp = float(traffic.get("ramp_s", 0))
    horizon = ramp + seconds
    if traffic["arrivals"] not in ("closed", "poisson"):
        raise ValueError(f"unknown arrival process {traffic['arrivals']!r}")
    closed = traffic["arrivals"] == "closed"
    if closed and docs_cfg:
        raise ValueError("a closed loop over documents: nothing bounds what it "
                         "pushes, so the store pool cannot be sized")

    def requests():
        """Block after block, without end; arrival times for an open loop."""
        walk = docs_cfg.get("stratum", 10) if docs_cfg else 0   # past the probed stratum
        t = 0.0
        while True:
            t_l = apportion(tails_w, block)
            o_l = apportion(outs_w, block)
            order.shuffle(t_l)
            order.shuffle(o_l)
            kinds = ["new"] * block
            new_l: List[int] = []
            if docs_cfg:
                n_re = round(block * docs_cfg["reask_share"])
                kinds = ["reask"] * n_re + ["new"] * (block - n_re)
                order.shuffle(kinds)
                new_l = apportion(docs_cfg["lengths"], block - n_re)
                order.shuffle(new_l)
            gaps = [0.0] * block if closed else gap_quantiles(block, cell["rate"])
            order.shuffle(gaps)
            for kind, tl, ol, gap in zip(kinds, t_l, o_l, gaps):
                t += gap
                if kind == "reask":
                    doc = population[walk % len(population)]
                    walk += 1
                else:
                    doc = toks(new_l.pop()) if docs_cfg else []
                yield {"due": None if closed else t, "kind": kind,
                       "body": body(doc + toks(tl), ol)}

    # a closed loop draws its next request when a client is free: the
    # schedule is the generator itself, the same sequence for the same seed
    schedule = requests() if closed else list(
        itertools.takewhile(lambda r: r["due"] < horizon, requests()))
    return dict(blocker=blocker, warm_decode=warm_decode, fill=fill,
                probes=probes, warm_reask=warm_reask, schedule=schedule,
                ramp_s=ramp, closed=closed,
                meta={"population_docs": len(population),
                      "population_tokens": sum(len(d) for d in population),
                      "cache_tokens": cache_tokens, "width_buckets": buckets,
                      "batch_buckets": batches})


def _first_of_each_length(population: List[List[int]]) -> List[int]:
    seen, out = set(), []
    for i, d in enumerate(population):
        if len(d) not in seen:
            seen.add(len(d))
            out.append(i)
    return out
