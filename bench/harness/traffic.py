"""The one generator that turns a traffic file's parameters and a seed
into the inputs of a run.

Site jobs follow the rigid-job model of Lublin and Feitelson ("The
workload on parallel supercomputers: modeling the characteristics of
rigid jobs", JPDC 63(11), 2003), with the batch-job parameters the
traffic file holds (``jobs``):

* size: serial (one node) with ``serial_prob``; else ``2**u`` nodes with
  ``u`` from a two-stage uniform over ``[ulow, umed]`` (with ``uprob``)
  and ``[umed, uhi]``, ``uhi = log2(site nodes)``, ``umed = uhi -
  umed_below_uhi``; ``u`` rounded to a whole power of two with
  ``pow2_prob``;
* runtime: ``exp`` of a hyper-gamma, ``Gamma(a1, b1)`` with
  probability ``p = pa * nodes + pb`` (clipped to [0, 1]), else
  ``Gamma(a2, b2)`` (shape, scale);
* walltime, the user's estimate: ``runtime / accuracy`` with the
  accuracy uniform on (0, 1] (``estimate``), both capped at the site's
  walltime limit.  A job runs its runtime, not its walltime.

A serial job asks one core of one socket of one node; a parallel job
asks whole nodes, every socket and core of each.

The model draws a fixed pool of jobs from ``jobs["pool_seed"]``; a
run's seed only orders it, block by block: every block of ``block``
jobs holds the same jobs for every seed, in an order the seed shuffles
(with ``block`` 1, the pool's own order for every seed).  So every seed
gets the same sizes and runtimes, and a run's amount of work does not
swing with its seed.  Poisson arrival gaps are drawn the
same way, from evenly spaced quantiles of the exponential.
"""
from __future__ import annotations

import math
import random
from dataclasses import dataclass
from typing import Iterator, List, Tuple

import numpy as np


@dataclass(frozen=True)
class SiteJob:
    index: int
    nodes: int
    sockets_per_node: int         # asked of each node
    cores_per_socket: int         # asked of each socket
    runtime: float                # seconds it runs
    walltime: float               # seconds it asks for (>= runtime)


def _job_nodes(rng: random.Random, m: dict, site_nodes: int) -> int:
    if rng.random() < m["serial_prob"]:
        return 1
    uhi = math.log2(site_nodes)
    umed = uhi - m["umed_below_uhi"]
    if rng.random() < m["uprob"]:
        u = rng.uniform(m["ulow"], umed)
    else:
        u = rng.uniform(umed, uhi)
    if rng.random() < m["pow2_prob"]:
        u = math.floor(u + 0.5)
    return max(2, min(site_nodes, int(2.0 ** u + 0.5)))


def _job_runtime(rng: random.Random, m: dict, nodes: int) -> float:
    p = min(1.0, max(0.0, m["pa"] * nodes + m["pb"]))
    if rng.random() < p:
        g = rng.gammavariate(m["a1"], m["b1"])
    else:
        g = rng.gammavariate(m["a2"], m["b2"])
    return math.exp(g)


def job_pool(traffic: dict, site: dict) -> List[Tuple[int, int, int, float,
                                                       float]]:
    """The model's fixed pool: (nodes, sockets asked per node, cores
    asked per socket, runtime, walltime) per job."""
    m = traffic["jobs"]
    rng = random.Random(m["pool_seed"])
    limit = float(m["walltime_limit_s"])
    spn, cps = site["sockets_per_node"], site["cores_per_socket"]
    pool = []
    for _ in range(m["pool_size"]):
        n = _job_nodes(rng, m, site["nodes"])
        runtime = min(limit, max(1.0, _job_runtime(rng, m, n)))
        accuracy = 1.0 - rng.random()                  # (0, 1]
        walltime = min(limit, runtime / accuracy)
        shape = (1, 1) if n == 1 else (spn, cps)
        pool.append((n, *shape, runtime, walltime))
    return pool


def site_jobs(traffic: dict, site: dict, seed: int) -> Iterator[SiteJob]:
    """Endless stream of site jobs: the pool, block by block in an
    order the seed shuffles, again and again."""
    pool = job_pool(traffic, site)
    block = traffic["jobs"]["block"]
    rng = random.Random(seed)
    index = 0
    while True:
        for lo in range(0, len(pool), block):
            part = pool[lo:lo + block]
            rng.shuffle(part)
            for n, s, c, runtime, walltime in part:
                yield SiteJob(index, n, s, c, runtime, walltime)
                index += 1


def mean_job_node_seconds(traffic: dict, site: dict) -> float:
    """Mean nodes x runtime over the pool: the work a job brings."""
    pool = job_pool(traffic, site)
    return sum(n * r for n, _, _, r, _ in pool) / len(pool)


def arrival_gaps(traffic: dict, site: dict, seed: int,
                 block: int = 64) -> Iterator[float]:
    """Exponential gaps whose mean offers ``traffic["load"]`` of the
    site's node-seconds: evenly spaced quantiles, shuffled."""
    mean = mean_job_node_seconds(traffic, site) / (traffic["load"]
                                                   * site["nodes"])
    rng = random.Random(seed ^ 0x5EED)
    q = [-math.log(1.0 - (i + 0.5) / block) * mean for i in range(block)]
    while True:
        gaps = list(q)
        rng.shuffle(gaps)
        yield from gaps


def prompts(traffic: dict, vocab: int, seed: int,
            batch_index: int) -> np.ndarray:
    """Token ids ``[batch, prompt_len]`` of one closed-loop batch."""
    rng = np.random.default_rng([seed, batch_index])
    return rng.integers(0, vocab, (traffic["batch"], traffic["prompt_len"]),
                        dtype=np.int32)


def sample_rows(n_batches: int, batch: int, k: int, seed: int) -> List[int]:
    """``k`` finished requests drawn from the seed, as flat indices
    ``batch_index * batch + row``: one from each of ``k`` equal stripes
    of the batch's rows, each from a batch drawn at random, so that a
    fault confined to part of a batch cannot miss the sample."""
    if n_batches == 0:
        return []
    rng = np.random.default_rng([seed, 0xC0FFEE])
    edges = np.linspace(0, batch, k + 1).astype(int)
    out = []
    for lo, hi in zip(edges[:-1], edges[1:]):
        if hi > lo:
            out.append(int(rng.integers(n_batches)) * batch
                       + int(rng.integers(lo, hi)))
    return sorted(out)


def jax_key_seed(seed: int) -> Tuple[int, int]:
    """Two 32-bit words from any whole-number seed, for a JAX key."""
    s = np.random.SeedSequence(seed).generate_state(2)
    return int(s[0]), int(s[1])
